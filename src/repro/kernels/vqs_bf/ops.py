"""Public entry point: Pallas on TPU, interpret-mode elsewhere."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.engine.streams import PolicyResult, SchedStreams, \
    resolve_work_steps
from repro.kernels.common import interpret_default

from .ref import vqs_bf_ref
from .vqs_bf import vqs_bf_pallas, vqs_bf_vmem_bytes  # noqa: F401


def vqs_bf_simulate(streams: SchedStreams, J: int, L: int, K: int,
                    Qcap: int, A_max: int, work_steps: int | None = None,
                    window: int | None = None,
                    use_pallas: bool = True) -> PolicyResult:
    """Fused-kernel Monte-Carlo VQS-BF: one grid cell per ensemble member.

    streams holds (G, ...)-shaped pre-generated randomness
    (engine.streams.make_streams vmapped over the ensemble keys)."""
    work_steps = resolve_work_steps(work_steps, A_max)
    if not use_pallas:
        return vqs_bf_ref(streams.n, streams.sizes, streams.durs, J=J, L=L,
                          K=K, Qcap=Qcap, A_max=A_max,
                          work_steps=work_steps)
    qlen, occ, ndep, dropped, trunc, _ = vqs_bf_pallas(
        streams.n, streams.sizes, streams.durs, J=J, L=L, K=K, Qcap=Qcap,
        A_max=A_max, work_steps=work_steps, window=window,
        interpret=interpret_default())
    z = jnp.zeros_like(dropped)  # kernels simulate fault-free clusters
    return PolicyResult(qlen, occ, jnp.cumsum(ndep, axis=1), dropped, trunc,
                        z, z, z)
