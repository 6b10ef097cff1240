"""Pre-generated randomness streams shared by every accelerator engine.

All engine randomness is hoisted out of the slot loops into ``SchedStreams``:
per-slot arrival counts, job sizes and service durations, generated either

  * from a PRNG key (``make_streams``) with exactly the key chain of the
    original in-loop reference engine, so stream-consuming engines reproduce
    it bit-for-bit; or
  * from a workload trace (``streams_from_trace``), so Google-like traces
    (core/trace.py) replay through the same fixed-shape engines that run the
    synthetic Monte-Carlo studies.

The duration stream layout is shared across policies: the LAST ``A_max``
lanes of ``durs[t]`` belong to the slot's arrivals (``durs[t, -A_max + a]``
is arrival ``a``'s duration — consumed by BF-J placements, and by the VQS
engines, which attach the duration to the job at arrival), while everything
before them is the sequential-draw region consumed dc-th-placement-first by
the BF-J/S engines' BF-S refills.  ``make_streams`` emits the full
``L*K + A_max`` width; ``streams_from_trace`` emits only the per-arrival
lanes — a trace has no meaningful sequential region (BF-S refills would
detach durations from job identities), so the BF-J/S engines statically
reject trace-shaped streams instead of replaying them wrong.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

INF_SLOT = jnp.iinfo(jnp.int32).max


class SchedStreams(NamedTuple):
    """Per-slot randomness consumed by the scheduling engines.

    Generated with exactly the key chain of the in-loop reference engine, so
    engines consuming these streams reproduce ``engine="reference"``
    bit-for-bit.  (Known historically as ``BFJSStreams`` — the layout is
    policy-generic and the old name remains as an alias.)

    The canonical size layout is ``(T, A_max, R)`` — one requirement vector
    per arrival.  Single-resource streams (``R == 1``) squeeze the resource
    axis away and keep the historical ``(T, A_max)`` plane, so every
    existing engine, kernel and test consumes exactly the layout it always
    did; ``num_resources`` reads R off the shape.
    """
    n: jax.Array       # (T,) int32 arrival counts, already clipped to A_max
    sizes: jax.Array   # (T, A_max) f32 sizes in (0,1] — (T, A_max, R) if R>1
    durs: jax.Array    # (T, L*K + A_max) int32 geometric service durations
    #: Optional per-slot server fault plane: ``up[t, i]`` is True when
    #: server ``i`` is functional during slot ``t``.  ``None`` (the default)
    #: means a fault-free cluster — the engines skip the preemption step
    #: entirely, so un-faulted trajectories are bit-identical to streams
    #: that never carried the plane.  Generated by ``make_streams(
    #: fault_rate=, repair_rate=)`` or injected from a trace of
    #: down/recovery events (``fault_plane_from_events``).
    up: jax.Array | None = None

    @property
    def num_resources(self) -> int:
        """R: 1 for the squeezed legacy layout, trailing dim otherwise.

        Anchored on ``durs``'s rank (always one axis fewer than an
        R-carrying ``sizes``) so it also reads correctly on ensemble-batched
        streams with a leading G axis."""
        return 1 if self.sizes.ndim == self.durs.ndim \
            else int(self.sizes.shape[-1])


#: Back-compat alias (PR 1 public name).
BFJSStreams = SchedStreams


class PolicyResult(NamedTuple):
    """Per-slot trajectory of one simulated cluster (any policy/engine).

    Single-resource policies keep ``occupancy`` as the historical ``(T,)``
    plane; multi-resource policies (``bfjs-mr``) report one occupancy plane
    per resource, ``(T, R)`` — total occupied capacity in servers, per
    resource, exact on the ``quantize.RES`` grid."""
    queue_len: jax.Array   # (T,) int32
    occupancy: jax.Array   # (T,) f32 occupied capacity (servers); (T, R)
    #                        per-resource planes for multi-resource policies
    departed: jax.Array    # (T,) int32 cumulative departures
    dropped: jax.Array     # () int32 arrivals dropped by fixed-size buffers
    truncated: jax.Array   # () int32 slots where a fixed bound cut the
    #                        policy short (0 == bit-exact vs. the reference)
    # Fault accounting (all () int32, zero on fault-free streams; always
    # ``preempted == requeued + lost`` — a preemption either requeues the
    # job with one more retry or loses it, never silently):
    preempted: jax.Array | None = None  # jobs evicted by server-down shocks
    requeued: jax.Array | None = None   # preemptions re-admitted to a queue
    lost: jax.Array | None = None       # preemptions past the retry bound
    #                                     (or with no queue space left)
    # Streaming backpressure (set only by ``core.engine.streaming``; both
    # None on one-shot/chunked runs and EXCLUDED from bit-match comparisons
    # — they measure host/device overlap, not the trajectory):
    chunks_behind: int | None = None    # chunks where the device finished
    #                                     before the host had the next chunk
    #                                     staged (ingest is the bottleneck)
    host_stall_us: float | None = None  # total host time blocked waiting on
    #                                     device compute (device is the
    #                                     bottleneck; the healthy state)
    # Supervision accounting (set only by supervised streaming runs —
    # ``stream_policy(..., supervisor=Supervisor(...))`` — all None
    # otherwise and EXCLUDED from bit-match comparisons; the self-healing
    # contract is that recovery never silently alters these):
    retries: int | None = None          # transient-failure retry attempts
    #                                     (ingestion + staging + checkpoint
    #                                     writes) that eventually succeeded
    quarantined: int | None = None      # poison chunks written to the
    #                                     quarantine dir and skipped — each
    #                                     is also a loud SupervisorWarning
    rollbacks: int | None = None        # corrupt checkpoint boundaries
    #                                     skipped on resume to reach the
    #                                     last good one
    # Work counters of ``bfjs-mr`` (() int32 per member, summed over the
    # slots; None for the other policies):
    steps: jax.Array | None = None      # work steps of the bounded
    #                                     placement work list (the oracle
    #                                     reports the steps its placements
    #                                     need: one per BF-S placement, one
    #                                     per arrival's BF-J attempt, at
    #                                     least one a slot — equal to the
    #                                     engines' while truncated == 0)
    bfs_placements: jax.Array | None = None  # placements made by BF-S
    #                                     refills rather than by BF-J at
    #                                     arrival (a policy quantity: every
    #                                     engine reports the same value)
    fit_chunks: jax.Array | None = None  # queue chunks the bfjs-mr
    #                                     kernel's BF-S fit search scanned
    #                                     (``kernels.bfjs_mr``); None from
    #                                     every other engine and EXCLUDED
    #                                     from bit-match comparisons, like
    #                                     the streaming counters


#: Back-compat alias (PR 1 public name).
BFJSResult = PolicyResult


def _geometric(key: jax.Array, mu: float, shape=()) -> jax.Array:
    """Geometric(mu) service durations, >= 1 slot.  ``mu`` is a Python
    float: the scale ``1 / log1p(-mu)`` is rounded to f32 once, on the host,
    and multiplied in.  Dividing by ``log1p(-mu)`` on the device gave
    different draws for the same key eagerly and inside a jitted program:
    XLA turns a division by a compile-time constant into a multiply by its
    reciprocal, off by an ulp, and ``ceil`` sometimes flips on that ulp."""
    u = jax.random.uniform(key, shape, minval=1e-7, maxval=1.0)
    scale = np.float32(1.0 / math.log1p(-mu))
    return jnp.maximum(jnp.ceil(jnp.log(u) * scale), 1.0).astype(jnp.int32)


#: fold_in tag of the fault-plane key: derived from the stream key but off
#: the per-slot chain, so adding faults never perturbs n/sizes/durs.
_FAULT_KEY_TAG = 0xFA17


@functools.partial(jax.jit,
                   static_argnames=("L", "horizon", "fault_rate",
                                    "repair_rate"))
def make_fault_plane(key: jax.Array, L: int, horizon: int,
                     fault_rate: float, repair_rate: float) -> jax.Array:
    """Two-state Markov capacity-shock plane: ``(T, L)`` bool, True = up.

    Every server starts up; an up server goes down with probability
    ``fault_rate`` per slot, a down server recovers with probability
    ``repair_rate`` per slot (stationary availability
    ``repair / (fault + repair)``).  Drawn from ``fold_in(key,
    _FAULT_KEY_TAG)`` — a branch off the stream key, so the job streams of
    ``make_streams(key, ...)`` stay bitwise identical with or without the
    plane, and the reference engines can regenerate the exact plane from
    the same key.
    """
    u = jax.random.uniform(jax.random.fold_in(key, _FAULT_KEY_TAG),
                           (horizon, L))

    def step(up_prev, u_t):
        up_t = jnp.where(up_prev, u_t >= fault_rate, u_t < repair_rate)
        return up_t, up_t

    _, up = jax.lax.scan(step, jnp.ones((L,), bool), u)
    return up


def fault_plane_from_events(events, horizon: int, L: int) -> jax.Array:
    """Build a ``(T, L)`` up-plane from a trace of capacity-shock events.

    ``events`` is an iterable of ``(slot, server, up)`` triples (e.g. the
    machine-events table of a cluster trace): server ``server`` changes to
    state ``up`` at ``slot`` and keeps it until its next event.  Servers
    start up; events outside ``[0, horizon)`` or ``[0, L)`` raise instead
    of being dropped silently."""
    plane = np.ones((horizon, L), dtype=bool)
    for slot, server, up in sorted(events, key=lambda e: int(e[0])):
        slot, server = int(slot), int(server)
        if not 0 <= slot < horizon:
            raise ValueError(f"fault event at slot {slot} outside horizon "
                             f"[0, {horizon})")
        if not 0 <= server < L:
            raise ValueError(f"fault event for server {server} outside "
                             f"[0, {L})")
        plane[slot:, server] = bool(up)
    return jnp.asarray(plane)


def with_fault_plane(streams: SchedStreams, up) -> SchedStreams:
    """Attach an explicit ``(T, L)`` up-plane to existing streams (e.g. a
    trace replay) after validating the time axis."""
    up = jnp.asarray(up, bool)
    T = int(streams.n.shape[-1])
    if up.ndim != 2 or up.shape[0] != T:
        raise ValueError(
            f"fault plane must be (T={T}, L), got {tuple(up.shape)}")
    return streams._replace(up=up)


@functools.partial(
    jax.jit,
    static_argnames=("lam", "mu", "sampler", "L", "K", "A_max", "horizon",
                     "num_resources", "fault_rate", "repair_rate"))
def make_streams(key: jax.Array, lam: float, mu: float,
                 sampler: Callable[[jax.Array, int], jax.Array],
                 L: int, K: int, A_max: int, horizon: int,
                 num_resources: int = 1, fault_rate: float = 0.0,
                 repair_rate: float = 1.0) -> SchedStreams:
    """Pre-generate all per-slot randomness for one cluster simulation.

    Replicates the reference engine's per-slot key chain
    (``key, _, k_n, k_sizes, k_dur = split(key, 5)``) and draws each slot's
    Poisson count / sizes / durations under ``vmap`` — bitwise identical to
    the in-loop draws, but issued as three large batched RNG calls instead
    of ``5 * horizon`` tiny ones.

    With ``num_resources == 1`` (the default) the sampler returns ``(n,)``
    scalar sizes and the stream keeps the historical ``(T, A_max)`` layout.
    With R > 1 the sampler returns ``(n, R)`` requirement vectors and the
    size stream is ``(T, A_max, R)``; the key chain is unchanged, so the
    non-size streams stay bitwise identical across R.

    ``fault_rate > 0`` additionally attaches a ``(T, L)`` server fault
    plane (``make_fault_plane`` — a two-state Markov chain drawn from a
    fold of the same key), turning the streams into a fault-injected
    cluster simulation; the job streams are bitwise unchanged by it.

    ``lam`` and ``mu`` are static: every caller, eager or inside its own
    jitted program, compiles the same constants into the draws, so a key
    gives the same streams everywhere (``ensemble_streams`` outside a
    Monte-Carlo program reproduces the streams inside it).
    """

    def chain(k, _):
        ks = jax.random.split(k, 5)
        return ks[0], ks[1:]

    _, ks = jax.lax.scan(chain, key, None, length=horizon)
    n = jnp.minimum(jax.vmap(lambda k: jax.random.poisson(k, lam))(ks[:, 1]),
                    A_max).astype(jnp.int32)
    sizes = jax.vmap(lambda k: sampler(k, A_max))(ks[:, 2])
    expect = (horizon, A_max) if num_resources == 1 \
        else (horizon, A_max, num_resources)
    if tuple(sizes.shape) != expect:
        raise ValueError(
            f"sampler produced sizes of shape {tuple(sizes.shape)} for "
            f"num_resources={num_resources}: expected {expect} "
            "(sampler(key, n) must return (n,) for R == 1, (n, R) "
            "otherwise)")
    durs = jax.vmap(lambda k: _geometric(k, mu, (L * K + A_max,)))(ks[:, 3])
    if fault_rate < 0 or repair_rate < 0:
        raise ValueError(
            f"fault_rate/repair_rate must be >= 0, got "
            f"({fault_rate}, {repair_rate})")
    up = None if fault_rate == 0.0 else make_fault_plane(
        key, L=L, horizon=horizon, fault_rate=fault_rate,
        repair_rate=repair_rate)
    return SchedStreams(n, sizes, durs, up)


def streams_from_trace(trace_or_slots, sizes=None, durations=None, *,
                       horizon: int | None = None,
                       A_max: int | None = None,
                       collapse: bool = True,
                       num_resources: int | None = None) -> SchedStreams:
    """Build ``SchedStreams`` that replay a workload trace exactly.

    Accepts either raw arrays ``(arrival_slots, sizes, durations)`` — with
    ``sizes`` of shape ``(N,)`` for scalar jobs or ``(N, R)`` for
    requirement vectors — or a ``core.trace.Trace`` directly:

        streams_from_trace(trace)                  # max(cpu, mem), paper's
                                                   # collapse preprocessing
        streams_from_trace(trace, collapse=False)  # (cpu, mem) uncollapsed,
                                                   # (T, A_max, 2) sizes for
                                                   # policy="bfjs-mr"

    Mirrors ``core.simulator.simulate_trace`` preprocessing bit-for-bit:
    jobs are stably sorted by arrival slot, float sizes are quantized with
    ``quantize.to_grid`` per resource (the stream stores the exact grid
    value ``g/RES``, which float32 represents exactly for ``RES = 2**16``,
    so the engines' in-loop quantization recovers ``g`` verbatim) and
    durations are clamped to >= 1 slot.

    The duration stream holds ONLY the per-arrival lanes (``(T, A_max)``):
    every job's duration travels with the job, which is exactly the
    semantics of policies that attach durations at arrival (VQS, bfjs-mr).
    The single-resource BF-J/S engines additionally need a sequential-draw
    region that a trace cannot provide (their BF-S refills would detach
    durations from job identities), so they reject trace-shaped streams
    with a ValueError at trace time instead of replaying them wrong.

    ``A_max`` defaults to the trace's actual max arrivals-per-slot so no
    arrival is ever silently dropped; passing a smaller ``A_max`` is an
    error rather than a truncation.

    ``num_resources`` pins the R the caller's engine config expects
    (``Workload.num_resources``): a trace whose resource count disagrees
    raises with both shapes named instead of letting a squeezed or
    truncated plane broadcast into the wrong engine downstream.
    """
    from ..quantize import RES, to_grid

    if sizes is None or hasattr(trace_or_slots, "arrival_slots"):
        trace = trace_or_slots
        if sizes is not None or durations is not None:
            raise TypeError(
                "pass either a Trace or (arrival_slots, sizes, durations), "
                "not both")
        arrival_slots = np.asarray(trace.arrival_slots)
        durations = np.asarray(trace.durations)
        if collapse:
            sizes = np.maximum(trace.cpu, trace.mem)
        else:
            sizes = np.stack([trace.cpu, trace.mem], axis=1)
    else:
        arrival_slots = np.asarray(trace_or_slots)

    arrival_slots = np.asarray(arrival_slots)
    order = np.argsort(arrival_slots, kind="stable")
    arrival_slots = arrival_slots[order].astype(np.int64)
    sizes = np.asarray(sizes)
    R = 1 if sizes.ndim == 1 else int(sizes.shape[1])
    if num_resources is not None and R != num_resources:
        hint = ""
        if num_resources == 1 and R > 1:
            hint = " (or pass collapse=True)"
        elif R == 1 and num_resources == 2:
            hint = " (or pass collapse=False)"
        raise ValueError(
            f"trace carries R={R} resource plane(s) (sizes shape "
            f"{tuple(sizes.shape)}) but the target workload expects "
            f"num_resources={num_resources}; pass a matching trace"
            f"{hint} instead of broadcasting")
    g = to_grid(sizes[order])
    durations = np.maximum(np.asarray(durations)[order].astype(np.int64), 1)
    if horizon is None:
        if len(arrival_slots) == 0:
            raise ValueError(
                "empty trace and no horizon: pass horizon= explicitly")
        horizon = int(arrival_slots[-1]) + 1

    in_h = (arrival_slots >= 0) & (arrival_slots < horizon)
    counts = np.bincount(arrival_slots[in_h], minlength=horizon)[:horizon]
    peak = int(counts.max()) if len(counts) else 0
    if A_max is None:
        A_max = max(peak, 1)
    elif peak > A_max:
        raise ValueError(
            f"trace has {peak} arrivals in one slot > A_max={A_max}; "
            "raise A_max (streams never drop trace jobs silently)")

    size_shape = (horizon, A_max) if R == 1 else (horizon, A_max, R)
    size_arr = np.zeros(size_shape, dtype=np.float32)
    dur_arr = np.ones((horizon, A_max), dtype=np.int32)
    slot = arrival_slots[in_h]
    # lane[i] = index of job i within its slot (jobs are slot-sorted)
    lane = np.arange(len(slot)) - np.repeat(np.cumsum(counts) - counts,
                                            counts)
    size_arr[slot, lane] = (g[in_h].astype(np.float64) / RES).astype(np.float32)
    dur_arr[slot, lane] = durations[in_h]
    return SchedStreams(jnp.asarray(counts, jnp.int32),
                        jnp.asarray(size_arr),
                        jnp.asarray(dur_arr))


def resolve_work_steps(work_steps: int | None, A_max: int) -> int:
    """Default bound of the per-slot placement work lists: enough for every
    landed arrival plus a burst of refills; the ``truncated`` counter
    reports the (rare) slots where this was short."""
    return work_steps if work_steps is not None else A_max + 4


#: Back-compat alias (PR 1 private name, imported by kernels/bfjs).
_resolve_work_steps = resolve_work_steps
