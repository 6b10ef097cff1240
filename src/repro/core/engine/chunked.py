"""Crash-safe chunked policy sweeps over the scan engines.

Long Monte-Carlo horizons run as a sequence of T-chunks: each chunk is one
``lax.scan`` over ``chunk`` slots whose COMPLETE carry (server planes,
queue planes, retry/seq planes, counters, ``up_last``) is persisted with
:mod:`repro.checkpoint.ckpt` at every chunk boundary — atomic
tmp-then-rename directories, so a SIGKILL at ANY point leaves either the
previous or the next complete checkpoint on disk, never a torn one.
``resume=True`` restores the newest boundary and continues; because the
scan carry is the engine's entire state (fault recovery detection included
— ``up_last`` lives in the carry, not in a shifted stream plane), an
interrupted-and-resumed sweep is BIT-IDENTICAL to a straight-through run.

The driver refuses engines other than ``"scan"`` upstream
(``api.run_policy_streams``): the reference oracles keep host-side state
that cannot be checkpointed, and the Pallas kernels keep theirs in VMEM
scratch.  Checkpoints are validated on resume — policy, horizon, chunk
length, engine config and a SHA-256 fingerprint of the streams must all
match, so a checkpoint can never silently continue a different sweep.

Per-chunk ``departed`` restarts at zero (it is an output, not carry); the
driver re-offsets each chunk by the previous cumulative total.  The scalar
deviation/fault counters (``dropped``, ``truncated``, ``preempted``,
``requeued``, ``lost``) accumulate inside the carry, so the final chunk's
values are already whole-horizon totals.

Monte-Carlo sweeps chunk too: ensemble-batched streams (a leading G axis
on every plane, ``sharding.ensemble_streams``) run the per-chunk scan
VMAPPED over the ensemble — and, with ``mesh=``, shard_mapped so each
device owns its G/D members (``core.engine.sharding``).  The per-chunk
carry keeps the full ``(G, ...)`` shape in the checkpoint (carries are
donated on-device but persisted host-side), and the manifest never pins a
device count: a sweep checkpointed on D devices resumes bit-exactly on D'
for any D' dividing G (DESIGN.md §11).
"""
from __future__ import annotations

import hashlib
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt

from .streams import PolicyResult, SchedStreams


def _bfjs_stateful(streams, state, config):
    from .bfjs import run_bfjs_streams
    return run_bfjs_streams(streams, state=state, return_state=True,
                            **config)


def _vqs_stateful(streams, state, config):
    from .vqs import run_vqs_streams
    return run_vqs_streams(streams, state=state, return_state=True,
                           **config)


def _bfjs_mr_stateful(streams, state, config):
    from .bfjs_mr import run_bfjs_mr_streams
    return run_bfjs_mr_streams(streams, state=state, return_state=True,
                               **config)


def _vqs_bf_stateful(streams, state, config):
    from .vqs_bf import run_vqs_bf_streams
    return run_vqs_bf_streams(streams, state=state, return_state=True,
                              **config)


_STATEFUL: dict[str, Callable] = {
    "bfjs": _bfjs_stateful,
    "vqs": _vqs_stateful,
    "bfjs-mr": _bfjs_mr_stateful,
    "vqs-bf": _vqs_bf_stateful,
}


def streams_fingerprint(streams: SchedStreams) -> str:
    """SHA-256 over every stream plane (dtype, shape and bytes) — the
    resume guard that a checkpoint only ever continues its own sweep."""
    h = hashlib.sha256()
    for name, arr in zip(streams._fields, tuple(streams)):
        if arr is None:
            h.update(f"{name}:none;".encode())
        else:
            a = np.asarray(arr)
            h.update(f"{name}:{a.dtype}:{a.shape};".encode())
            h.update(a.tobytes())
    return h.hexdigest()


def _slice_streams(streams: SchedStreams, lo: int, hi: int,
                   ensemble: bool = False) -> SchedStreams:
    sl = (slice(None), slice(lo, hi)) if ensemble else slice(lo, hi)
    return streams._replace(
        n=streams.n[sl], sizes=streams.sizes[sl], durs=streams.durs[sl],
        up=None if streams.up is None else streams.up[sl])


def _append(partial: PolicyResult | None, res: PolicyResult,
            axis: int = 0) -> PolicyResult:
    if partial is None:
        return res
    dep_off = partial.departed[..., -1:] if axis else partial.departed[-1]
    return PolicyResult(
        jnp.concatenate([partial.queue_len, res.queue_len], axis=axis),
        jnp.concatenate([partial.occupancy, res.occupancy], axis=axis),
        jnp.concatenate([partial.departed, res.departed + dep_off],
                        axis=axis),
        res.dropped, res.truncated, res.preempted, res.requeued, res.lost,
        steps=res.steps, bfs_placements=res.bfs_placements)


def _save_step(checkpoint_dir: str, step: int, payload: Any,
               extra: dict) -> None:
    """One chunk-boundary save (factored out so crash tests can intercept
    the exact boundary); ``step - 1`` is the chunk it follows."""
    ckpt.save(checkpoint_dir, step, payload, extra=extra, chunk=step - 1)


def _load_step(checkpoint_dir: str, step: int
               ) -> tuple[tuple, PolicyResult]:
    """Rebuild (scan state, partial result) from a boundary checkpoint.

    The engine state is an anonymous tuple whose structure is
    policy-/config-dependent, so restore by npz key layout rather than a
    ``like`` pytree: ``state/<i>`` leaves in index order and
    ``partial/<field>`` leaves by ``PolicyResult`` field name.

    Reads go through ``ckpt.load_arrays`` — checksum-verified, so a
    truncated or garbled file raises a typed
    :class:`~repro.checkpoint.ckpt.CheckpointCorruptError` naming the
    path (never a raw pickle/zip/numpy error); supervised streaming
    catches exactly that type to roll back to the last good boundary.
    """
    path = os.path.join(checkpoint_dir, f"step_{step:08d}", "arrays.npz")
    data = ckpt.load_arrays(checkpoint_dir, step)
    idxs = sorted(int(k.split("/", 1)[1]) for k in data
                  if k.startswith("state/"))
    if idxs != list(range(len(idxs))) or not idxs:
        raise ckpt.CheckpointCorruptError(
            path, f"state indices {idxs} are not a dense 0..N range")
    state = tuple(jnp.asarray(data[f"state/{i}"]) for i in idxs)
    # Optional fields (fault counters on unfaulted runs, the streaming
    # backpressure/supervision counters always) are None leaves — dropped
    # by tree_flatten at save time, so absent from the npz.
    partial = PolicyResult(*(
        jnp.asarray(data[f"partial/{f}"])
        if f"partial/{f}" in data else None
        for f in PolicyResult._fields))
    return state, partial


def run_chunked(streams: SchedStreams, *, policy: str = "bfjs",
                chunk: int, checkpoint_dir: str | None = None,
                resume: bool = False,
                stop_after_chunks: int | None = None,
                mesh=None, **config) -> PolicyResult:
    """Run a scan-engine sweep in crash-safe chunks (see module docstring).

    ``stop_after_chunks`` ends the run early after that many chunks have
    been EXECUTED this call (checkpoints included) — the hook crash tests
    use to stop at an arbitrary boundary; the partial result is returned.

    Streams with a leading ensemble axis (``n.ndim == 2``) run the
    per-chunk scan vmapped over the ensemble; ``mesh=`` additionally
    shards that axis over devices (``core.engine.sharding``).
    """
    if policy not in _STATEFUL:
        raise ValueError(
            f"policy {policy!r} has no stateful scan engine; chunked "
            f"sweeps support: {', '.join(sorted(_STATEFUL))}")
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    if resume and checkpoint_dir is None:
        raise ValueError("resume=True needs checkpoint_dir=")
    ensemble = streams.n.ndim == 2
    if mesh is not None and not ensemble:
        raise ValueError("mesh= needs ensemble-batched streams (a leading "
                         "G axis on every plane); single-run streams have "
                         "nothing to shard")
    if policy == "bfjs-mr":
        from .bfjs_mr import _lift_sizes
        from .workload import normalize_capacity
        streams = _lift_sizes(streams)
        config["capacity"] = normalize_capacity(
            config.get("capacity", 1.0), int(streams.sizes.shape[-1]))
    config.setdefault("A_max", int(streams.sizes.shape[streams.n.ndim]))
    T = int(streams.n.shape[-1])
    bounds = [(lo, min(lo + chunk, T)) for lo in range(0, T, chunk)]
    meta = {
        "policy": policy,
        "horizon": T,
        "chunk": int(chunk),
        "n_chunks": len(bounds),
        "faulted": streams.up is not None,
        "streams_sha256": streams_fingerprint(streams),
        "config": {k: repr(v) for k, v in sorted(config.items())},
    }

    start = 0
    state: tuple | None = None
    partial: PolicyResult | None = None
    if resume:
        latest = ckpt.latest_step(checkpoint_dir)
        if latest is not None:
            extra = ckpt.read_manifest(checkpoint_dir, latest)["extra"]
            stale = {k: (extra.get(k), v) for k, v in meta.items()
                     if extra.get(k) != v}
            if stale:
                raise ValueError(
                    f"checkpoint at {checkpoint_dir!r} belongs to a "
                    f"different sweep; mismatched (found, expected): "
                    f"{stale}")
            if latest > len(bounds):
                raise ValueError(
                    f"checkpoint step {latest} exceeds the sweep's "
                    f"{len(bounds)} chunks")
            state, partial = _load_step(checkpoint_dir, latest)
            start = latest

    runner = _STATEFUL[policy]
    if ensemble:
        base = runner

        def _first(s):
            return jax.vmap(lambda x: base(x, None, config))(s)

        def _next(s, st):
            return jax.vmap(lambda x, y: base(x, y, config))(s, st)

        if mesh is not None:
            from jax.sharding import PartitionSpec as P
            from .sharding import _check_divides
            _check_divides(int(streams.n.shape[0]), mesh)
            spec = P(mesh.axis_names[0])
            out = (spec, spec)
            _first = jax.shard_map(_first, mesh=mesh, in_specs=(spec,),
                                   out_specs=out, check_vma=False)
            _next = jax.shard_map(_next, mesh=mesh, in_specs=(spec, spec),
                                  out_specs=out, check_vma=False)
        # jit once per run so every chunk reuses the compilation; the
        # previous chunk's carry is donated — its buffers back the next
        # chunk's state in place.
        _first = jax.jit(_first)
        _next = jax.jit(_next, donate_argnums=(1,))

        def runner(streams_chunk, st, _cfg):
            if st is None:
                return _first(streams_chunk)
            return _next(streams_chunk, st)

    executed = 0
    for i in range(start, len(bounds)):
        if stop_after_chunks is not None and executed >= stop_after_chunks:
            break
        lo, hi = bounds[i]
        res, state = runner(_slice_streams(streams, lo, hi, ensemble),
                            state, config)
        partial = _append(partial, res, axis=1 if ensemble else 0)
        executed += 1
        if checkpoint_dir is not None:
            _save_step(checkpoint_dir, i + 1,
                       {"state": state, "partial": partial}, meta)
    if partial is None:
        raise ValueError("nothing to run: empty horizon or "
                         "stop_after_chunks=0 with no checkpoint")
    return partial
