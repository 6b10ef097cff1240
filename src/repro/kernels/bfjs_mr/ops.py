"""Public entry point: Pallas on TPU, interpret-mode elsewhere."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.engine.streams import PolicyResult, SchedStreams, \
    resolve_work_steps
from repro.core.engine.workload import capacity_plane
from repro.kernels.common import interpret_default

from .bfjs_mr import bfjs_mr_pallas, bfjs_mr_vmem_bytes  # noqa: F401
from .ref import bfjs_mr_ref


def _lift_batched_sizes(streams: SchedStreams) -> SchedStreams:
    """The kernel consumes (G, T, A_max, R) sizes; lift squeezed R=1
    ensemble streams (same contract as engine.bfjs_mr._lift_sizes)."""
    if streams.sizes.ndim == streams.durs.ndim:
        return streams._replace(sizes=streams.sizes[..., None])
    return streams


def bfjs_mr_simulate(streams: SchedStreams, L: int, K: int, Qcap: int,
                     A_max: int, work_steps: int | None = None,
                     capacity=1.0,
                     window: int | None = None,
                     use_pallas: bool = True,
                     early_exit: bool = True) -> PolicyResult:
    """Fused-kernel Monte-Carlo multi-resource BF-J/S: one grid cell per
    ensemble member.

    streams holds (G, ...)-shaped pre-generated randomness
    (engine.streams.make_streams vmapped over the ensemble keys, or a
    trace-built stream batched with a leading axis).  ``capacity`` is a
    scalar, a length-R tuple or an ``(L, R)`` per-server plane.
    ``early_exit=False``
    forces the kernel's placement work list to run its full
    ``work_steps`` bound every slot (the pre-optimization behaviour, kept
    for benchmarking the early-exit win — trajectories are identical)."""
    streams = _lift_batched_sizes(streams)
    work_steps = resolve_work_steps(work_steps, A_max)
    if not use_pallas:
        return bfjs_mr_ref(streams.n, streams.sizes, streams.durs, L=L,
                           K=K, Qcap=Qcap, A_max=A_max,
                           work_steps=work_steps, capacity=capacity)
    cap = capacity_plane(capacity, L, int(streams.sizes.shape[-1]))
    qlen, occ, ndep, dropped, trunc, steps, bfs, fit = bfjs_mr_pallas(
        streams.n, streams.sizes, streams.durs, jnp.asarray(cap), L=L, K=K,
        Qcap=Qcap, A_max=A_max, work_steps=work_steps, window=window,
        interpret=interpret_default(), early_exit=early_exit)
    z = jnp.zeros_like(dropped)  # kernels simulate fault-free clusters
    return PolicyResult(qlen, occ, jnp.cumsum(ndep, axis=1), dropped, trunc,
                        z, z, z, steps=steps, bfs_placements=bfs,
                        fit_chunks=fit)
