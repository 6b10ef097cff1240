"""Pure-jnp oracle for the fused multi-resource BF-J/S slot-step kernel.

The oracle IS the production scan engine (engine.bfjs_mr.run_bfjs_mr_streams)
vmapped over the ensemble dimension — the kernel must reproduce its
trajectories exactly (and that engine is itself bit-parity-tested against
the event-driven ``MultiResourceBFJS`` numpy oracle)."""
from __future__ import annotations

import jax

from repro.core.engine.bfjs_mr import run_bfjs_mr_streams
from repro.core.engine.streams import PolicyResult, SchedStreams


def bfjs_mr_ref(n, sizes, durs, L: int, K: int, Qcap: int, A_max: int,
                work_steps: int | None = None,
                capacity=1.0) -> PolicyResult:
    """n (G, T) int32, sizes (G, T, A_max, R) f32, durs (G, T, D) int32 ->
    PolicyResult with (G, ...)-shaped fields."""

    def one(n1, s1, d1):
        return run_bfjs_mr_streams(SchedStreams(n1, s1, d1), L=L, K=K,
                                   Qcap=Qcap, A_max=A_max,
                                   work_steps=work_steps, capacity=capacity)

    return jax.vmap(one)(n, sizes, durs)
