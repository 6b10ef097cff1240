"""The chunk driver: carried-state runs of the scan engines, chunk by chunk.

Every other entry point replays a fixed-``T`` pre-materialized stream in
one launch; the paper's setting (Psychas–Ghaderi 2019, Section III) is an
*unbounded* arrival process served online.  :func:`stream_policy`
iterates chunks of any — possibly infinite — ``SchedStreams`` iterator
through the stateful scan engines (``core.engine.chunked._STATEFUL``),
threading the complete carried queue/occupancy/fault state between
chunks, so

    **a chunked run of any finite stream is BIT-IDENTICAL to the
    one-shot ``run_policy_streams`` run, under any chunking** —

the invariant ``tests/test_streaming.py`` enforces per policy x engine x
chunk size.  It is the one chunk driver: the checkpointed sweeps of
``run_policy_streams(chunk=)`` and ``monte_carlo_policy(chunk=)`` run
through it too, fed by :func:`iter_stream_chunks`.  Around the carry it
keeps a *pipeline*:

  * **Double-buffered ingestion.**  JAX dispatch is asynchronous, so while
    the device computes chunk N the host pulls chunk N+1 from the iterator
    and stages it with ``jax.device_put``.  At most two chunks are ever in
    flight (the host blocks on chunk N-1 before dispatching N+1), which
    bounds host memory for infinite iterators to O(2 chunks), not O(T).
  * **Backpressure counters.**  The returned :class:`PolicyResult` carries
    ``chunks_behind`` — chunks whose device compute finished before the
    host had the NEXT chunk staged (ingestion is the bottleneck; feed the
    device bigger chunks or a faster reader) — and ``host_stall_us`` — the
    total host time spent blocked waiting on device compute (the device is
    the bottleneck; the healthy state for a serving loop).  Both measure
    host/device overlap only: they are excluded from bit-match
    comparisons, and the trajectory never depends on timing.
    ``host_stall_us`` covers the pipeline's drain only: with
    ``checkpoint_dir=`` the host mostly waits for the device inside the
    checkpoint instead, and that wait is the ``repro.ckpt.wait`` span.
  * **Profiler spans.**  Each phase of a chunk is a
    ``jax.profiler.TraceAnnotation`` with the chunk's index as its
    ``chunk`` stat: ``repro.ingest.chunk`` (re-bucketing trace rows into
    the chunk), ``repro.stream.stage`` (validating and ``device_put``),
    ``repro.stream.wait`` (the drain, what ``host_stall_us`` counts),
    ``repro.stream.checkpoint`` (the whole boundary save) and, inside it,
    ``repro.ckpt.wait`` / ``repro.ckpt.fetch`` / ``repro.ckpt.write``
    (``repro.checkpoint.ckpt.save``).  They are recorded only while a
    profiler trace runs, and cost under a microsecond each otherwise.
    Ingestion and staging count source chunks, the drain and the
    checkpoint executed chunks; the two differ only after a quarantine.
  * **Bounded-memory trajectories.**  ``trajectory="full"`` concatenates
    per-chunk planes (the default; what the parity tests compare).
    ``trajectory="tail"`` keeps only the newest chunk's planes — with the
    cumulative ``departed`` offset folded in and the scalar counters
    already whole-run totals (they accumulate in the carry) — so an
    unbounded run holds O(chunk), not O(elapsed horizon).

Engines: ``"scan"`` is the native streaming engine (its carry is the
entire simulation state).  ``"pallas"`` routes through
``kernels.common.pallas_precheck(streaming_carry=True)`` — the fused
kernels keep state in VMEM scratch for one launch and cannot thread it
across chunks, so the request degrades loudly (GracefulDegradationWarning)
to the bit-identical scan engine, or raises under ``strict=True``.
``"reference"`` keeps host-side state and is rejected.

Ensemble-batched streams (a leading G axis on every plane,
``sharding.ensemble_streams``) run each chunk vmapped over the ensemble
and, with ``mesh=``, shard_mapped so each device owns its G/D members
(``core.engine.sharding``).

``checkpoint_dir=`` persists the complete carry and the partial result at
every chunk boundary (:mod:`repro.checkpoint.ckpt`: atomic
tmp-then-rename, so a SIGKILL at any point leaves a complete checkpoint);
``resume=True`` re-iterates the source, skips the chunks already executed
and continues bit-exactly.  The manifest pins policy, trajectory mode,
ensemble layout, fault plane, config and the first chunk's SHA-256 —
plus, for :func:`iter_stream_chunks`, horizon, chunk length and a SHA-256
of every stream plane — so a checkpoint never continues a different sweep
or stream; it never pins a device count (DESIGN.md §9, §11).

``supervisor=`` (a :class:`~repro.core.engine.supervisor.Supervisor`)
makes the loop self-healing — retry/backoff on transient ingestion,
staging and checkpoint-write failures, watchdog timeouts on device
compute and host staging, rollback over corrupt checkpoints on resume,
poison-chunk quarantine — and ``audit=True`` turns on the per-chunk
jitted invariant auditor.  Both are opt-in and leave the unsupervised
fast path byte-for-byte unchanged (DESIGN.md §14).
"""
from __future__ import annotations

import hashlib
import os
import time
from collections import deque
from typing import Any, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt

from .chunked import _STATEFUL
from .streams import PolicyResult, SchedStreams
from .supervisor import Supervisor, SupervisorTimeout, make_auditor

#: jitted ensemble (vmapped, and shard_mapped over a mesh) runner pairs
#: keyed by (policy, mesh, config items) — reused across stream_policy
#: calls so repeated chunked runs of the same study (sweeps, benches,
#: tests) compile once, like the module-level jits of the underlying
#: engines.  jax.jit then re-specializes per chunk shape as usual.
_ENSEMBLE_RUNNERS: dict = {}


def _ensemble_runners(policy: str, config: dict, mesh=None):
    try:
        key = (policy, mesh, tuple(sorted(config.items())))
        cached = _ENSEMBLE_RUNNERS.get(key)
    except TypeError:        # unhashable config value: skip the cache
        key, cached = None, None
    if cached is not None:
        return cached
    base, cfg = _STATEFUL[policy], dict(config)

    def first(s):
        return jax.vmap(lambda x: base(x, None, cfg))(s)

    def next_(s, st):
        return jax.vmap(lambda x, y: base(x, y, cfg))(s, st)

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        spec = P(mesh.axis_names[0])
        first = jax.shard_map(first, mesh=mesh, in_specs=(spec,),
                              out_specs=(spec, spec), check_vma=False)
        next_ = jax.shard_map(next_, mesh=mesh, in_specs=(spec, spec),
                              out_specs=(spec, spec), check_vma=False)
    # the previous chunk's carry is donated: its buffers back the next
    # chunk's state in place
    pair = (jax.jit(first), jax.jit(next_, donate_argnums=(1,)))
    if key is not None:
        _ENSEMBLE_RUNNERS[key] = pair
    return pair


def streams_fingerprint(streams: SchedStreams) -> str:
    """SHA-256 over every stream plane (dtype, shape and bytes) — the
    resume guard that a checkpoint only ever continues its own stream."""
    h = hashlib.sha256()
    for name, arr in zip(streams._fields, tuple(streams)):
        if arr is None:
            h.update(f"{name}:none;".encode())
        else:
            a = np.asarray(arr)
            h.update(f"{name}:{a.dtype}:{a.shape};".encode())
            h.update(a.tobytes())
    return h.hexdigest()


def _save_step(checkpoint_dir: str, step: int, payload: Any,
               extra: dict) -> None:
    """One chunk-boundary save, the driver's only one (a module global so
    crash tests can intercept the exact boundary); ``step - 1`` is the
    chunk it follows."""
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
    path (never a raw pickle/zip/numpy error); supervised runs catch
    exactly that type to roll back to the last good boundary.
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


def iter_stream_chunks(streams: SchedStreams, chunk: int
                       ) -> Iterator[SchedStreams]:
    """Slice a materialized ``SchedStreams`` into contiguous time chunks —
    the chunk source of checkpointed sweeps (``run_policy_streams(chunk=)``,
    ``monte_carlo_policy(chunk=)``) and of tests and benches replaying an
    in-memory stream through :func:`stream_policy`.  Ensemble-batched
    streams (leading G axis) slice along their time axis."""
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    return _StreamSlices(streams, int(chunk))


class _StreamSlices:
    """The iterator behind :func:`iter_stream_chunks`.  The whole stream
    is in hand, so :meth:`manifest` can describe all of it;
    :func:`stream_policy` pins that in its checkpoints."""

    def __init__(self, streams: SchedStreams, chunk: int):
        self.streams, self.chunk = streams, chunk
        self.horizon = int(streams.n.shape[-1])
        self.lo = 0

    def __iter__(self):
        return self

    def __next__(self) -> SchedStreams:
        s, lo = self.streams, self.lo
        if lo >= self.horizon:
            raise StopIteration
        self.lo = hi = min(lo + self.chunk, self.horizon)
        sl = slice(lo, hi) if s.n.ndim == 1 else (slice(None), slice(lo, hi))
        return s._replace(n=s.n[sl], sizes=s.sizes[sl], durs=s.durs[sl],
                          up=None if s.up is None else s.up[sl])

    def manifest(self) -> dict:
        """What a checkpoint of this source pins: horizon, chunk length
        and the fingerprint of every stream plane."""
        return {"horizon": self.horizon, "chunk": self.chunk,
                "streams_sha256": streams_fingerprint(self.streams)}


def stream_chunks_from_trace(traces: Iterable, *, chunk_slots: int,
                             A_max: int, collapse: bool = True,
                             num_resources: int | None = None
                             ) -> Iterator[SchedStreams]:
    """Re-bucket an iterator of :class:`~repro.core.trace.Trace` chunks
    (e.g. ``core.trace.iter_trace_csv`` output, chunked by ROW COUNT) into
    fixed ``chunk_slots``-slot ``SchedStreams`` windows for
    :func:`stream_policy`.

    The two chunkings disagree by construction — a CSV reader cuts on
    rows, the engines need contiguous time windows — so arrivals are
    buffered until a window's end has provably passed (arrival slots are
    non-decreasing across reader chunks; the reader validates that) and
    emitted window by window, INCLUDING all-empty windows for slot gaps
    longer than a window: time must advance for in-service durations to
    tick.  Only the not-yet-emitted rows are ever held — constant memory.

    ``A_max`` is mandatory: a streaming source cannot know the global
    per-slot arrival peak in advance, and the engines' carry must keep one
    shape across chunks.  A window whose peak exceeds it raises (streams
    never drop trace jobs silently).  The final window is trimmed to the
    last arrival's slot, so the concatenated horizon equals the one-shot
    ``streams_from_trace`` horizon and trajectories bit-match.

    ``collapse=True`` applies the paper's max(cpu, mem) preprocessing;
    ``collapse=False`` keeps (cpu, mem) requirement vectors
    (``policy="bfjs-mr"``).  ``num_resources`` pins the expected R exactly
    as ``streams_from_trace`` does.

    The returned iterator is a CLASS, not a generator, on purpose: a
    failure raised by the inner ``traces`` source propagates without
    killing the re-bucketing state, so when the source is itself
    idempotent-on-failure (``core.trace.ResumableTraceReader``) the whole
    composition is retryable by the streaming supervisor — and a
    ``skip()`` on the source is forwarded for poison-chunk quarantine.
    """
    if chunk_slots <= 0:
        raise ValueError(f"chunk_slots must be positive, got {chunk_slots}")
    R = 1 if collapse else 2
    if num_resources is not None and num_resources != R:
        raise ValueError(
            f"collapse={collapse} yields R={R} resource plane(s) but "
            f"num_resources={num_resources} was requested")
    return _TraceChunkSource(iter(traces), chunk_slots, A_max, collapse,
                             num_resources)


class _TraceChunkSource:
    """The re-bucketing iterator behind :func:`stream_chunks_from_trace`.

    State (arrival buffer, window cursor, pending completed windows) only
    advances on a SUCCESSFUL pull from the inner source, so an exception
    from ``next(traces)`` leaves this iterator retryable — re-calling
    ``__next__`` re-attempts the same inner pull (the supervisor's
    idempotent-source contract, which a plain generator cannot satisfy).
    """

    def __init__(self, traces, chunk_slots: int, A_max: int,
                 collapse: bool, num_resources: int | None):
        self.traces = traces
        self.chunk_slots = chunk_slots
        self.A_max = A_max
        self.collapse = collapse
        self.num_resources = num_resources
        R = 1 if collapse else 2
        self.buf_slots = np.empty((0,), dtype=np.int64)
        self.buf_sizes = np.empty((0,) if collapse else (0, R),
                                  dtype=np.float64)
        self.buf_durs = np.empty((0,), dtype=np.int64)
        self.win_lo = 0      # first slot of the next window to emit
        self.last_slot = -1  # newest slot seen (slots are non-decreasing)
        self._pending: deque = deque()
        self._exhausted = False
        self._inner_failed = False
        self.returned = 0    # chunks returned so far

    def __iter__(self):
        return self

    def skip(self) -> None:
        """Advance the inner source past a poison chunk (supervised
        quarantine protocol) when it supports skipping."""
        skip = getattr(self.traces, "skip", None)
        if skip is not None:
            skip()

    def _emit(self, hi_slots: int) -> SchedStreams:
        """Emit the window [win_lo, win_lo + hi_slots) from the buffer."""
        from .streams import streams_from_trace

        take = self.buf_slots < self.win_lo + hi_slots
        win = streams_from_trace(
            self.buf_slots[take] - self.win_lo, self.buf_sizes[take],
            self.buf_durs[take], horizon=hi_slots, A_max=self.A_max,
            num_resources=self.num_resources)
        self.buf_slots = self.buf_slots[~take]
        self.buf_sizes = self.buf_sizes[~take]
        self.buf_durs = self.buf_durs[~take]
        self.win_lo += hi_slots
        return win

    def __next__(self) -> SchedStreams:
        with jax.profiler.TraceAnnotation("repro.ingest.chunk",
                                          chunk=self.returned):
            chunk = self._next_window()
        self.returned += 1
        return chunk

    def _next_window(self) -> SchedStreams:
        import types
        while not self._pending and not self._exhausted:
            try:
                tr = next(self.traces)
            except StopIteration:
                if self._inner_failed \
                        and isinstance(self.traces, types.GeneratorType):
                    # a plain generator dies on its first error; its
                    # post-failure StopIteration is death, not a clean end
                    from .supervisor import SupervisorError
                    raise SupervisorError(
                        "trace source raised StopIteration right after "
                        "failing: a plain generator dies on its first "
                        "error and cannot be retried — wrap the source "
                        "in a resumable reader (e.g. "
                        "core.trace.ResumableTraceReader)") from None
                self._exhausted = True
                if len(self.buf_slots):
                    # final window: trim to the last arrival so the
                    # concatenated horizon equals the one-shot
                    # streams_from_trace horizon
                    self._pending.append(
                        self._emit(self.last_slot - self.win_lo + 1))
                break
            except BaseException:
                self._inner_failed = True
                raise
            self._inner_failed = False
            slots = np.asarray(tr.arrival_slots, dtype=np.int64)
            if len(slots) == 0:
                continue
            if slots[0] < self.last_slot:
                raise ValueError(
                    f"trace chunks went backwards in time: slot "
                    f"{slots[0]} after {self.last_slot} (the reader "
                    "guarantees monotone arrivals — did chunks arrive "
                    "out of order?)")
            sizes = (np.maximum(tr.cpu, tr.mem) if self.collapse
                     else np.stack([tr.cpu, tr.mem], axis=1))
            self.buf_slots = np.concatenate([self.buf_slots, slots])
            self.buf_sizes = np.concatenate([self.buf_sizes, sizes])
            self.buf_durs = np.concatenate(
                [self.buf_durs, np.asarray(tr.durations, np.int64)])
            self.last_slot = int(slots[-1])
            # every window whose end has provably passed is complete
            while self.last_slot >= self.win_lo + self.chunk_slots:
                self._pending.append(self._emit(self.chunk_slots))
        if self._pending:
            return self._pending.popleft()
        raise StopIteration


def _chunk_shape(streams: SchedStreams) -> tuple:
    """(ensemble?, G, A_max lanes, R) — the shape a stream's chunks must
    keep constant (the engine carry is built once, from the first)."""
    ensemble = streams.n.ndim == 2
    G = int(streams.n.shape[0]) if ensemble else 0
    R = streams.num_resources
    return (ensemble, G, int(streams.sizes.shape[streams.n.ndim]), R)


def stream_policy(chunks: Iterable, *, policy: str = "bfjs",
                  engine: str = "scan",
                  checkpoint_dir: str | None = None,
                  resume: bool = False,
                  stop_after_chunks: int | None = None,
                  trajectory: str = "full",
                  strict: bool = False,
                  supervisor: Supervisor | None = None,
                  audit: bool = False,
                  mesh=None,
                  **config) -> PolicyResult:
    """Run a (possibly infinite) iterator of ``SchedStreams`` chunks
    through a stateful scan engine with carried state — see the module
    docstring for the pipeline, invariants and backpressure semantics.

    ``chunks`` yields contiguous time windows (``iter_stream_chunks``,
    ``stream_chunks_from_trace``, or any generator — windows may have
    different lengths, but must keep one arrival-lane width and, for
    ensembles, one G).  ``stop_after_chunks`` bounds how many chunks THIS
    call executes (the unbounded-generator escape hatch; the partial
    result is returned and, with ``checkpoint_dir=``, resumable).
    ``trajectory="tail"`` keeps only the newest chunk's per-slot planes
    (bounded memory; scalar counters stay whole-run exact).

    Bit-match contract: for any finite chunking of streams ``S``,
    ``stream_policy(iter_stream_chunks(S, c), policy=p)`` equals
    ``run_policy_streams(S, policy=p)`` bit-for-bit on every trajectory
    field, for every chunk size ``c``.

    ``supervisor=`` turns on the self-healing layer (retry/backoff,
    watchdogs, checkpoint rollback, poison-chunk quarantine — see
    ``core.engine.supervisor``); its counters land on the result's
    ``retries``/``quarantined``/``rollbacks`` fields.  Transient-fault
    recovery preserves the bit-match contract exactly; only a QUARANTINED
    chunk (deterministic poison, always counted, never silent) changes
    the trajectory vs. the unperturbed run.  ``audit=True`` checks the
    runtime conservation laws after every chunk (jitted margins; the
    check syncs the pipeline once per chunk) and raises a typed
    ``InvariantViolation`` naming chunk and counter.

    ``mesh=`` (a 1-D ``jax.sharding.Mesh``, ``sharding.resolve_mesh``)
    shards the ensemble axis of ensemble-batched chunks over its devices.
    """
    if policy not in _STATEFUL:
        raise ValueError(
            f"policy {policy!r} has no stateful scan engine; chunked "
            f"runs support: {', '.join(sorted(_STATEFUL))}")
    if trajectory not in ("full", "tail"):
        raise ValueError(f"trajectory must be 'full' or 'tail', "
                         f"got {trajectory!r}")
    if engine == "reference":
        raise ValueError(
            'engine="reference" keeps host-side state and cannot stream; '
            'use engine="scan"')
    if engine == "pallas":
        from repro.kernels.common import pallas_precheck
        # never True: the fused kernels' state lives in VMEM scratch for
        # one launch only — raises under strict, else warns + scan
        pallas_precheck(f"{policy} stream", nbytes=0, streaming_carry=True,
                        strict=strict)
        engine = "scan"
    elif engine != "scan":
        raise ValueError(f"unknown engine {engine!r}; streaming supports "
                         '"scan" (and "pallas" via loud fallback)')
    if resume and checkpoint_dir is None:
        raise ValueError("resume=True needs checkpoint_dir=")

    sup = supervisor
    it = iter(chunks)

    def pull(index: int):
        """``next(it)`` — supervised: retried with backoff on transient
        (retryable) errors, each attempt under the staging watchdog.  A
        plain generator dies on the FIRST error it raises; detecting its
        premature ``StopIteration`` on retry turns silent stream
        truncation into a loud failure."""
        if sup is None:
            return next(it)
        failed = False

        def attempt():
            nonlocal failed
            import types
            try:
                return next(it)
            except StopIteration:
                # a resumable source may legitimately end right after a
                # recovered failure; a PLAIN generator cannot — it died
                if failed and isinstance(it, types.GeneratorType):
                    from .supervisor import SupervisorError
                    raise SupervisorError(
                        f"chunk source raised StopIteration while "
                        f"retrying chunk {index}: a plain generator dies "
                        "on its first error and cannot be retried — wrap "
                        "the source in a resumable reader (e.g. "
                        "core.trace.ResumableTraceReader)") from None
                raise
            except BaseException:
                failed = True
                raise

        return sup.call("chunk ingestion", attempt, chunk_index=index,
                        timeout=sup.stage_timeout)

    try:
        first = pull(0)
    except StopIteration:
        raise ValueError("stream_policy: the chunk iterator is empty") \
            from None

    ensemble, G, lanes, n_res = _chunk_shape(first)
    if mesh is not None:
        if not ensemble:
            raise ValueError(
                "mesh= needs ensemble-batched streams (a leading G axis on "
                "every plane); single-run streams have nothing to shard")
        from .sharding import _check_divides
        _check_divides(G, mesh)
    if policy == "bfjs-mr":
        from .workload import normalize_capacity
        config["capacity"] = normalize_capacity(
            config.get("capacity", 1.0), max(n_res, 1))
    config.setdefault("A_max", lanes)
    from .tuning import apply_tuned
    apply_tuned(policy, "scan", config, max(n_res, 1))
    config.pop("window", None)

    meta = {
        "policy": policy,
        "trajectory": trajectory,
        "ensemble": ensemble,
        "faulted": first.up is not None,
        "first_chunk_sha256": None,  # filled below (after lifting)
        "config": {k: repr(v) for k, v in sorted(config.items())},
    }
    describe = getattr(it, "manifest", None)
    if checkpoint_dir is not None and describe is not None:
        meta.update(describe())

    def prepare(streams_chunk: SchedStreams, index: int) -> SchedStreams:
        """Host-side chunk staging: validate shape, lift bfjs-mr planes,
        push to the device.  This is the work double-buffered against the
        previous chunk's device compute."""
        shape = _chunk_shape(streams_chunk)
        if shape != (ensemble, G, lanes, n_res):
            raise ValueError(
                f"chunk {index} changed shape mid-stream: (ensemble, G, "
                f"A_max, R) {shape} != first chunk's "
                f"{(ensemble, G, lanes, n_res)} — the engine carry keeps "
                "one shape for the life of the stream")
        if policy == "bfjs-mr":
            from .bfjs_mr import _lift_sizes
            streams_chunk = _lift_sizes(streams_chunk)
        return jax.device_put(streams_chunk)

    base = _STATEFUL[policy]
    if ensemble:
        _first_fn, _next_fn = _ensemble_runners(policy, config, mesh)

        def runner(streams_chunk, st):
            return _first_fn(streams_chunk) if st is None \
                else _next_fn(streams_chunk, st)
    else:
        def runner(streams_chunk, st):
            return base(streams_chunk, st, config)

    def stage(chunk, index: int):
        """``prepare`` — supervised: retried transients, staging
        watchdog."""
        with jax.profiler.TraceAnnotation("repro.stream.stage",
                                          chunk=index):
            if sup is None:
                return prepare(chunk, index)
            return sup.call("chunk staging",
                            lambda: prepare(chunk, index),
                            chunk_index=index, timeout=sup.stage_timeout)

    def pull_staged(index: int):
        """Pull + stage source chunk ``index``.  Under supervision, a
        chunk that still fails after retries — or fails staging with a
        non-retryable error (e.g. a mid-stream shape change) — is
        quarantined (when a quarantine_dir exists) and the next source
        chunk tried.  Returns ``(staged, source_index)``; raises
        ``StopIteration`` on exhaustion.

        Retry contract: a supervised source must be IDEMPOTENT on failure
        — re-calling ``next()`` after an error re-attempts the SAME chunk
        (``core.trace.ResumableTraceReader`` provides this for CSV
        readers; a plain generator dies instead, which ``pull`` detects).
        A source may additionally expose ``skip()`` to advance past a
        poison chunk after quarantine; without it, a deterministically
        failing position keeps failing and the consecutive-quarantine
        limit aborts the stream (a broken source, not isolated poison)."""
        idx = index
        while True:
            try:
                raw = pull(idx)
            except (StopIteration, SupervisorTimeout):
                raise
            except Exception as e:
                if sup is None or not isinstance(e, sup.retry.retryable):
                    raise
                sup.quarantine(idx, e, policy=policy, config=config)
                skip = getattr(it, "skip", None)
                if skip is not None:
                    skip()
                idx += 1
                continue
            try:
                staged_chunk = stage(raw, idx)
            except (StopIteration, SupervisorTimeout):
                raise
            except Exception as e:
                if sup is None:
                    raise
                sup.quarantine(idx, e, streams_chunk=raw, policy=policy,
                               config=config)
                idx += 1
                continue
            if sup is not None:
                sup.mark_chunk_ok()
            return staged_chunk, idx

    def finish(result: PolicyResult, behind: int,
               stall_us: float) -> PolicyResult:
        extra = dict(chunks_behind=behind, host_stall_us=stall_us)
        if sup is not None:
            extra.update(retries=sup.retries, quarantined=sup.quarantined,
                         rollbacks=sup.rollbacks)
        return result._replace(**extra)

    staged = stage(first, 0)
    src = 0  # source index of the newest pulled chunk (quarantines count)
    if checkpoint_dir is not None:
        meta["first_chunk_sha256"] = streams_fingerprint(staged)

    auditor = None
    if audit:
        auditor = make_auditor(policy=policy, config=config,
                               num_resources=max(n_res, 1))

        def arr_sum(s: SchedStreams):
            return jnp.asarray(s.n).sum(axis=-1)

        arr_cum = jnp.zeros_like(arr_sum(staged))
        audit_zero = arr_cum

    start = 0
    state: tuple | None = None
    partial: PolicyResult | None = None
    if resume:
        if sup is not None:
            # rollback: walk back over corrupt boundaries (counted on
            # PolicyResult.rollbacks + CheckpointRollbackWarning) to the
            # newest checkpoint that still verifies
            latest, corrupt = ckpt.latest_valid_step(checkpoint_dir)
            sup.note_rollback(corrupt, checkpoint_dir)
        else:
            # unsupervised: a corrupt newest checkpoint surfaces as a
            # typed CheckpointCorruptError from read_manifest/_load_step
            latest = ckpt.latest_step(checkpoint_dir)
        if latest is not None:
            extra = ckpt.read_manifest(checkpoint_dir, latest)["extra"]
            stale = {k: (extra.get(k), v) for k, v in meta.items()
                     if extra.get(k) != v}
            if stale:
                raise ValueError(
                    f"checkpoint at {checkpoint_dir!r} belongs to a "
                    f"different sweep (a different stream, policy or "
                    f"config); mismatched (found, expected): {stale}")
            state, partial = _load_step(checkpoint_dir, latest)
            start = latest
            # skip the chunks already executed (the source re-iterates
            # deterministically — poison chunks quarantine again under
            # supervision, keeping the alignment; chunk 0's fingerprint
            # was checked above)
            if audit:
                arr_cum = arr_cum + arr_sum(staged)
            skipped = 1  # `first` is executed chunk 0
            while skipped < start:
                try:
                    done, src = pull_staged(src + 1)
                except StopIteration:
                    raise ValueError(
                        f"checkpoint says {start} chunks were executed "
                        f"but the iterator ran out after {skipped} — "
                        "resuming a DIFFERENT (shorter) stream?") from None
                if audit:
                    arr_cum = arr_cum + arr_sum(done)
                skipped += 1
            if start >= 1:
                try:
                    staged, src = pull_staged(src + 1)
                except StopIteration:
                    # stream fully executed already: return the checkpoint
                    return finish(partial, 0, 0.0)

    def fold(part: PolicyResult | None, res: PolicyResult) -> PolicyResult:
        """The run so far with chunk ``res`` appended (``"tail"``: in its
        place); ``res.departed`` re-offset by the cumulative total."""
        if part is None:
            return res
        departed = res.departed + (part.departed[..., -1:] if ensemble
                                   else part.departed[-1])
        if trajectory == "tail":
            return res._replace(departed=departed)
        def cat(a, b):
            return jnp.concatenate([a, b], axis=1 if ensemble else 0)

        return res._replace(queue_len=cat(part.queue_len, res.queue_len),
                            occupancy=cat(part.occupancy, res.occupancy),
                            departed=cat(part.departed, departed))

    executed = 0
    chunks_behind = 0
    host_stall = 0.0
    inflight: deque = deque()  # (chunk index, representative leaf)
    i = start
    exhausted = False

    def drain_one() -> None:
        ck, leaf = inflight.popleft()
        with jax.profiler.TraceAnnotation("repro.stream.wait", chunk=ck):
            if sup is not None and sup.compute_timeout is not None:
                sup.watch("device compute",
                          lambda: jax.block_until_ready(leaf),
                          sup.compute_timeout, chunk_index=ck)
            else:
                jax.block_until_ready(leaf)

    while not exhausted:
        if stop_after_chunks is not None and executed >= stop_after_chunks:
            break
        # depth-2 pipeline: before dispatching chunk i, drain to at most
        # one incomplete dispatch; the time blocked here is device-bound
        # time — the healthy direction of backpressure.
        while len(inflight) > 1:
            t0 = time.perf_counter()
            drain_one()
            host_stall += time.perf_counter() - t0
        if audit:
            chunk_arr = arr_sum(staged)
        res, state = runner(staged, state)
        inflight.append((i, res.queue_len))
        ready_leaf = res.queue_len
        # host-side work overlapped against the device: pull + stage the
        # NEXT chunk while this one computes
        try:
            staged, src = pull_staged(src + 1)
        except StopIteration:
            exhausted = True
        if ready_leaf.is_ready() and not exhausted:
            chunks_behind += 1  # device idle before the host had chunk N+1
        if audit:
            dep_base = audit_zero if partial is None \
                else partial.departed[..., -1]
        partial = fold(partial, res)
        if audit:
            arr_cum = arr_cum + chunk_arr
            # the margins check syncs on this chunk's outputs — the price
            # of per-chunk auditing is one pipeline sync per chunk
            auditor(arr_cum, res, dep_base, chunk_index=i)
        executed += 1
        i += 1
        if checkpoint_dir is not None:
            # ckpt pulls arrays to host — synchronizes, trading pipeline
            # overlap for crash-safety at every boundary
            with jax.profiler.TraceAnnotation("repro.stream.checkpoint",
                                              chunk=i - 1):
                step, payload = i, {"state": state, "partial": partial}

                def save():
                    _save_step(checkpoint_dir, step, payload, meta)

                if sup is None:
                    save()
                else:
                    sup.call("checkpoint write", save,
                             chunk_index=step - 1)
    # drain the tail of the pipeline so a compute watchdog covers the
    # final dispatch too
    while inflight:
        drain_one()
    if partial is None:
        raise ValueError("nothing to run: stop_after_chunks=0 with no "
                         "checkpoint to return")
    return finish(partial, chunks_behind, host_stall * 1e6)
