"""Shared plumbing for the per-policy scheduler kernels.

Every scheduler kernel family (``kernels/bfjs``, ``kernels/vqs``, ...)
follows the same layout — ``<policy>.py`` holds the fused Pallas kernel,
``ref.py`` the pure-jnp oracle (the production scan engine vmapped over the
ensemble), ``ops.py`` the public entry point that dispatches Pallas on TPU
and interpret mode elsewhere.  The pieces they share live here: the
dispatch gate, the VMEM/HBM footprint estimators, the TPU-legal block
layout of the per-slot scalar planes and the exact in-kernel prefix sum.
"""
from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: f32 infeasibility sentinel used by the float kernels (~f32 max).
BIG = 3.4e38

#: The TPU compiler's default scoped-VMEM limit on a v5e, and the default
#: VMEM budget of the fused kernels: each kernel's ``*_vmem_bytes``
#: estimate (the persistent scratch planes, the double-buffered per-window
#: stream blocks and the compiler's spills, as the chip lays them out,
#: :func:`tile_bytes`) covers its whole allocation, so it must fit the
#: default limit.  Override the budget with the REPRO_VMEM_BUDGET_BYTES
#: environment variable (read at call time, so tests can monkeypatch the
#: environment); a kernel whose estimate exceeds the default limit requests
#: it (:func:`compiler_params`).
SCOPED_VMEM_BYTES = 16 * 1024 * 1024

#: Per-device HBM budget for the ensemble-resident planes of a Monte-Carlo
#: kernel launch (pre-generated streams in + per-slot trajectories out, all
#: scaled by the ensemble dimension G) on backends that report no memory
#: limit.  On a TPU the budget is the device's own
#: ``memory_stats()["bytes_limit"]``.  Unlike the VMEM scratch — which is
#: per grid cell and independent of G — this footprint grows with the
#: ensemble, and SHARDING divides it: a mesh over D devices holds G/D
#: members per device.  Override with the REPRO_HBM_BUDGET_BYTES
#: environment variable (read at call time).
HBM_BUDGET_BYTES = 16 * 1024 ** 3

#: The (sublane, lane) tile every 32-bit VMEM plane is padded to.
SUBLANES, LANES = 8, 128


class GracefulDegradationWarning(UserWarning):
    """A ``engine="pallas"`` request was served by the scan engine instead.

    Raised as a *warning* (never silently) when the fused kernel cannot run
    the request — VMEM scratch estimate over budget, or a feature the kernel
    does not implement (fault planes).  The scan engine is bit-identical, so
    results are unaffected; pass ``strict=True`` to get a hard error
    instead."""


def vmem_budget_bytes() -> int:
    """The enforced VMEM scratch budget (env-overridable, read per call)."""
    return int(os.environ.get("REPRO_VMEM_BUDGET_BYTES", SCOPED_VMEM_BYTES))


def hbm_budget_bytes() -> int:
    """The enforced per-device ensemble-plane budget (env-overridable;
    the device's own memory limit on a TPU)."""
    env = os.environ.get("REPRO_HBM_BUDGET_BYTES")
    if env is not None:
        return int(env)
    if jax.default_backend() == "tpu":
        return int(jax.devices()[0].memory_stats()["bytes_limit"])
    return HBM_BUDGET_BYTES


def pallas_precheck(kernel: str, *, nbytes: int, hbm_bytes: int = 0,
                    num_devices: int = 1, fault_plane: bool = False,
                    streaming_carry: bool = False,
                    strict: bool = False) -> bool:
    """Gate an ``engine="pallas"`` dispatch (DESIGN.md §8/§9/§11).

    Returns True when the fused kernel may run.  On a violation — estimated
    VMEM scratch ``nbytes`` over :func:`vmem_budget_bytes`, the PER-DEVICE
    share of the ensemble planes ``hbm_bytes / num_devices`` over
    :func:`hbm_budget_bytes`, a fault-plane request (the kernels simulate
    fault-free clusters only), or a streaming-carry request (the kernels'
    state lives in VMEM scratch for the launch only and cannot be threaded
    across chunks of a stream) — either raises ``ValueError``
    (``strict=True``) or emits a loud :class:`GracefulDegradationWarning`
    and returns False so the caller falls back to the bit-identical scan
    engine.  Never fail silently.

    ``hbm_bytes`` is the GLOBAL ensemble footprint (streams in + per-slot
    trajectories out, all carrying the full G axis) and ``num_devices`` the
    mesh size it is sharded over, so a request that overflows one device
    can still dispatch when the ensemble spans a mesh — the sharded path
    is checked per device, never against global G."""
    budget = vmem_budget_bytes()
    reason = None
    per_device = -(-hbm_bytes // max(num_devices, 1))
    if streaming_carry:
        reason = (f"kernel {kernel!r} keeps its simulation state in VMEM "
                  "scratch and cannot export/import the cross-chunk carry "
                  "a streaming run threads between chunks")
    elif fault_plane:
        reason = (f"kernel {kernel!r} does not implement fault-plane "
                  "preemption")
    elif nbytes > budget:
        reason = (f"kernel {kernel!r} needs ~{nbytes} bytes of VMEM "
                  f"scratch, over the {budget}-byte budget "
                  "(REPRO_VMEM_BUDGET_BYTES)")
    elif per_device > hbm_budget_bytes():
        reason = (f"kernel {kernel!r} needs ~{per_device} bytes of "
                  f"ensemble streams/trajectories per device "
                  f"({hbm_bytes} over {num_devices} device(s)), over the "
                  f"{hbm_budget_bytes()}-byte budget "
                  "(REPRO_HBM_BUDGET_BYTES); shard the ensemble over more "
                  "devices (mesh=/devices=) or shrink G")
    if reason is None:
        return True
    if strict:
        raise ValueError(
            f"{reason}; engine=\"pallas\" cannot honour this request "
            "(strict=True — rerun with engine=\"scan\" or strict=False)")
    warnings.warn(f"{reason}; falling back to the bit-identical scan "
                  "engine", GracefulDegradationWarning, stacklevel=3)
    return False


def interpret_default() -> bool:
    """Pallas interpret mode everywhere but real TPUs (correctness-grade)."""
    return jax.default_backend() != "tpu"


def ensemble_plane_bytes(G: int, T: int, *, stream_lanes: int,
                         out_lanes: int) -> int:
    """Global HBM footprint of one Monte-Carlo kernel launch: the (G, T,
    lanes) pre-generated stream planes in plus the (G, T, lanes) per-slot
    trajectory planes out (all 4-byte dtypes), plus the per-member scalar
    counters.  Divided by the mesh size in :func:`pallas_precheck` — the
    per-DEVICE share is what gets gated, so sharding the ensemble grows
    the feasible G envelope instead of tripping a global-G check."""
    return 4 * G * (T * (stream_lanes + out_lanes) + 2)


def resolve_windows(T: int, window: int | None) -> tuple[int, int]:
    """Split a horizon into equal VMEM-sized time windows.

    Every fused slot-step kernel runs on a ``(G, NW)`` grid — ensemble
    member x time window — with simulation state persisting in VMEM scratch
    across a member's sequentially-executed windows.  Returns ``(TW, NW)``
    (window length, window count); ``window=None`` means the whole horizon
    in one window, and a window that does not divide the horizon is an
    error (a ragged tail would replay slots twice).  On a TPU the window
    is also the sublane extent of the VMEM stream blocks, so it must be a
    multiple of 8 or the whole horizon."""
    TW = T if window is None else window
    if T % TW:
        raise ValueError(f"window {TW} must divide horizon {T}")
    return TW, T // TW


def tile_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of a 2-D 32-bit plane as the chip allocates it: rows
    padded to a multiple of 8 sublanes, columns to a multiple of 128
    lanes (a ``(L, 16)`` plane costs as much as ``(L, 128)``, a
    ``(1, Qcap)`` row as much as ``(8, Qcap)``)."""
    return 4 * (-(-rows // SUBLANES) * SUBLANES) * (-(-cols // LANES) * LANES)


def stream_block_bytes(TW: int, *lanes: int) -> int:
    """VMEM bytes of the per-window ``(TW, lanes)`` stream input blocks,
    double-buffered by the pipeline (the next window's block is fetched
    while the current one is consumed)."""
    return 2 * sum(tile_bytes(TW, n) for n in lanes)


def compiler_params(vmem_bytes: int) -> pltpu.CompilerParams:
    """Compiler parameters of a kernel whose VMEM estimate is
    ``vmem_bytes``: the scoped limit is raised to the estimate where it
    exceeds the default (a budget raised through REPRO_VMEM_BUDGET_BYTES),
    never lowered below it."""
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(vmem_bytes, SCOPED_VMEM_BYTES))


def slot_spec(TW: int) -> pl.BlockSpec:
    """SMEM block of one member's per-slot scalar plane for one window.

    The per-slot planes (arrival counts in; queue length, occupancy and
    departures out) are laid out ``(G, NW, 1, TW)`` (:func:`to_windows`) so
    the block's last two dims equal the array's, which the TPU tiling rule
    accepts for any ``TW``; living in SMEM they take dynamic scalar reads
    and writes at slot ``tt``."""
    return pl.BlockSpec((None, None, 1, TW), lambda g, w: (g, w, 0, 0),
                        memory_space=pltpu.SMEM)


def arrival_spec(TW: int, lanes: int) -> pl.BlockSpec:
    """SMEM block of one member's ``(TW, lanes)`` per-arrival stream window
    (sizes or durations), for kernels that walk a slot's arrivals in a
    loop: SMEM takes the dynamic ``[0, tt, a]`` scalar reads that a VMEM
    block refuses on its lane axis."""
    return pl.BlockSpec((1, TW, lanes), lambda g, w: (g, w, 0),
                        memory_space=pltpu.SMEM)


def counter_spec() -> pl.BlockSpec:
    """SMEM block of one member's ``(G, 1, 1)`` end-of-run counter."""
    return pl.BlockSpec((None, 1, 1), lambda g, w: (g, 0, 0),
                        memory_space=pltpu.SMEM)


def to_windows(x: jax.Array, TW: int) -> jax.Array:
    """``(G, T)`` per-slot plane -> the ``(G, T // TW, 1, TW)`` layout of
    :func:`slot_spec`."""
    G, T = x.shape
    return x.reshape(G, T // TW, 1, TW)


def slot_out_shape(G: int, T: int, TW: int, dtype) -> jax.ShapeDtypeStruct:
    """Output shape of a per-slot plane in the :func:`slot_spec` layout
    (reshape the result back with ``.reshape(G, T)``)."""
    return jax.ShapeDtypeStruct((G, T // TW, 1, TW), dtype)


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along the lanes of an int32 ``(1, N)`` row.

    Mosaic has no ``cumsum``; this is an exact compare-against-iota count:
    entry ``c`` sums ``x[r]`` over the rows ``r <= c`` of an ``(N, N)``
    mask (integer adds only — no matmul, whose f32 passes would round)."""
    n = x.shape[-1]
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(r <= c, x.astype(jnp.int32).T, 0), axis=0,
                   keepdims=True)
