"""BF-J/S cluster engines (paper Section IV) on the scan + Pallas stack.

Three engines share one trajectory semantics (see DESIGN.md):

  * ``engine="reference"`` — the original nested ``fori/while/cond`` program,
    kept verbatim as the behavioural oracle;
  * ``engine="scan"``      — the branch-free rewrite: all randomness is
    hoisted into pre-generated streams (``streams.make_streams``) and the
    per-slot BF-S/BF-J placement nest becomes a single bounded work-list
    scan of masked vectorized selects (no ``cond``, no data-dependent trip
    counts), so ``vmap`` over seeds vectorizes cleanly;
  * ``engine="pallas"``    — the fused slot-step kernel in ``kernels/bfjs``
    (residuals, departure times and the queue stay resident in VMEM; the
    Monte-Carlo ensemble is the kernel grid).

"scan" and "reference" produce bit-identical trajectories on the shared
random streams as long as the bounded work list does not saturate; the
``truncated`` field of the result counts slots where the bound cut BF-S
short (0 == exact).

Fixed-capacity redesign (documented deviation from the unbounded queueing
model): the queue is a ``Qcap``-slot buffer and arrivals beyond ``A_max`` per
slot are dropped AND COUNTED (``dropped`` in the result) — runs whose drop
count is nonzero must be treated as saturated, not stable.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .ops import first_empty_positions, server_sum, slot_sum
from .streams import (INF_SLOT, PolicyResult, SchedStreams, _geometric,
                      make_fault_plane, make_streams, resolve_work_steps)

BFJSResult = PolicyResult

#: Default bound on fault-driven requeues: a job evicted by a server-down
#: shock re-enters the queue until it has been preempted ``max_requeue``
#: times, then it is counted ``lost``.
DEFAULT_MAX_REQUEUE = 2


def _preempt_grid(srv, dep, tries, queue, qtry, up_t, max_requeue):
    """Evict every job resident on a down server (DESIGN.md §9).

    Shared verbatim by the scan engine and the reference oracle, so faulted
    trajectories bit-match for free.  Victims below the retry bound re-enter
    the queue in row-major ``(server, slot)`` order through the same
    first-empty admission rule as arrivals, carrying ``tries + 1``; the rest
    (bound exhausted, or queue full) are lost.  Returns the updated planes
    plus this slot's ``(n_preempted, n_requeued, n_lost)`` counts — always
    ``n_preempted == n_requeued + n_lost``.
    """
    Qcap = queue.shape[0]
    victim = (~up_t)[:, None] & (srv > 0.0)
    elig = (victim & (tries < max_requeue)).reshape(-1)
    pos, land = first_empty_positions(queue == 0.0, elig)
    at = jnp.where(land, pos, Qcap)
    queue = queue.at[at].set(jnp.where(land, srv.reshape(-1), 0.0),
                             mode="drop")
    qtry = qtry.at[at].set(jnp.where(land, tries.reshape(-1) + 1, 0),
                           mode="drop")
    n_vict = victim.sum().astype(jnp.int32)
    n_req = land.sum().astype(jnp.int32)
    srv = jnp.where(victim, 0.0, srv)
    dep = jnp.where(victim, INF_SLOT, dep)
    tries = jnp.where(victim, 0, tries)
    return srv, dep, tries, queue, qtry, n_vict, n_req, n_vict - n_req


def _check_sequential_durs(streams: SchedStreams, L: int, K: int,
                           A_max: int) -> None:
    """BF-J/S consumes a ``durs[t, :L*K]`` sequential-draw region that
    trace-built streams (``streams_from_trace``) deliberately lack — their
    BF-S refills would detach durations from job identities.  The width
    check is static (shape-only), so it raises at trace time even under
    jit/vmap instead of replaying the trace wrong."""
    width = streams.durs.shape[-1]
    if width != L * K + A_max:
        raise ValueError(
            f"BF-J/S needs a duration stream of width L*K + A_max = "
            f"{L * K + A_max} (sequential-draw region + per-arrival lanes), "
            f"got {width}.  Trace-built streams carry per-arrival durations "
            "only — replay traces through a policy that attaches durations "
            "at arrival (policy=\"vqs\").")


class BFJSState(NamedTuple):
    srv: jax.Array       # (L, K) float32 job sizes in servers (0 = empty slot)
    dep: jax.Array       # (L, K) int32 departure slot (INF_SLOT when empty)
    queue: jax.Array     # (Qcap,) float32 queued sizes (0 = empty)
    dropped: jax.Array   # () int32 arrivals dropped by the fixed-size buffer
    key: jax.Array
    # Fault-injection planes (zeros/ones on fault-free runs):
    qtry: jax.Array      # (Qcap,) int32 retry counts riding with queued jobs
    tries: jax.Array     # (L, K) int32 retry counts of resident jobs
    preempted: jax.Array  # () int32
    requeued: jax.Array   # () int32
    lost: jax.Array       # () int32
    up_last: jax.Array   # (L,) bool: previous slot's fault-plane row


@functools.partial(
    jax.jit, static_argnames=("L", "K", "Qcap", "A_max", "work_steps",
                              "max_requeue", "return_state"))
def run_bfjs_streams(streams: SchedStreams,
                     L: int, K: int, Qcap: int, A_max: int,
                     work_steps: int | None = None,
                     max_requeue: int = DEFAULT_MAX_REQUEUE,
                     state: tuple | None = None,
                     return_state: bool = False):
    """Branch-free BF-J/S slot engine over pre-generated streams.

    One ``lax.scan`` over slots; inside each slot the BF-S refill and BF-J
    placement passes are a single bounded work list (unrolled: ``work_steps``
    masked-select placement steps, no ``cond``, no data-dependent trip
    counts).  Each step dynamically dispatches: while any freed server still
    has a fitting queued job it performs the BF-S placement for the
    lowest-index such server, otherwise it attempts the next landed arrival
    (BF-J).  Jobs only ever leave the queue and placements only shrink
    residuals, so an exhausted server never un-exhausts and BF-S placements
    genuinely all precede BF-J attempts — the step order is identical to the
    reference engine's per-server ``while`` nest, but no step is wasted on a
    failed probe.

    Residuals are maintained incrementally yet exactly: a placement
    recomputes the target server's residual as ``1 - slot_sum(row)`` over
    the slot-ordered row, the same expression the reference engine and the
    kernel evaluate, so trajectories bit-match on every backend (as long as
    ``truncated`` stays 0).

    Streams carrying a fault plane (``streams.up is not None``) run the
    fault-injected variant: down servers evict their jobs (``_preempt_grid``
    — requeue under the ``max_requeue`` bound, lost past it), leave every
    placement-feasibility mask, and rejoin the BF-S freed set on recovery.
    Fault-free streams compile to exactly the historical program.

    ``state=`` / ``return_state=True`` thread the complete scan carry for
    crash-safe chunked sweeps (DESIGN.md §9): running the horizon in slices,
    feeding each slice the previous slice's returned state, reproduces the
    straight-through trajectory bit-for-bit.  Per-chunk ``departed`` restarts
    from 0 (the chunked driver offsets it); the scalar counters accumulate
    inside the carry.
    """
    horizon = streams.n.shape[0]
    faulted = streams.up is not None
    W = resolve_work_steps(work_steps, A_max)
    D = L * K + A_max
    _check_sequential_durs(streams, L, K, A_max)
    a_iota = jnp.arange(A_max)
    l_iota = jnp.arange(L)
    q_iota = jnp.arange(Qcap)
    k_iota = jnp.arange(K)

    def slot_step(state, inp):
        (srv, dep, queue, t, q_cnt, dropped, trunc,
         qtry, tries, preempted, requeued, lost, up_last) = state
        if faulted:
            n, sizes, durs, up_t = inp
        else:
            n, sizes, durs = inp

        # 1. departures
        leaving = dep == t
        freed = leaving.any(axis=1)
        n_dep = leaving.sum()
        srv = jnp.where(leaving, 0.0, srv)
        dep = jnp.where(leaving, INF_SLOT, dep)

        # 1b. capacity shocks: evict jobs resident on down servers
        # (requeue under the retry bound, lose the rest), drop down servers
        # from every placement mask, and treat recoveries as freed.
        if faulted:
            tries = jnp.where(leaving, 0, tries)
            srv, dep, tries, queue, qtry, n_p, n_r, n_l = _preempt_grid(
                srv, dep, tries, queue, qtry, up_t, max_requeue)
            preempted = preempted + n_p
            requeued = requeued + n_r
            lost = lost + n_l
            q_cnt = q_cnt + n_r
            freed = (freed | (up_t & ~up_last)) & up_t
            up_last = up_t
        resid = 1.0 - slot_sum(srv)[:, 0]

        # 2. arrivals -> first empty queue slots (record where they landed)
        n_empty = jnp.cumsum((queue == 0.0).astype(jnp.int32))
        pos_a = jnp.searchsorted(n_empty, a_iota + 1)  # a-th empty index
        landed = (a_iota < n) & (pos_a < Qcap)
        n_landed = landed.sum()
        dropped = dropped + n - n_landed
        q_cnt = q_cnt + n_landed
        queue = queue.at[jnp.where(landed, pos_a, Qcap)].set(
            jnp.where(landed, sizes, 0.0), mode="drop")
        new_pos = jnp.where(landed, pos_a, -1)
        # landed arrival indices, compacted ascending (for BF-J dispatch),
        # with their duration-stream entries pre-gathered.
        rank = jnp.cumsum(landed.astype(jnp.int32)) - 1
        landed_list = jnp.full((A_max,), A_max - 1, jnp.int32).at[
            jnp.where(landed, rank, A_max)].set(a_iota.astype(jnp.int32),
                                                mode="drop")
        pos_list = new_pos[landed_list]
        dur_list = durs[L * K + landed_list]

        # 3+4. BF-S then BF-J as one bounded, unrolled placement work list.
        # Index extraction uses min-of-masked-iota instead of argmax/argmin
        # (same first-index tie-breaks, but plain min/max reductions
        # vectorize on CPU where XLA's variadic arg-reduce does not).
        def work(carry):
            srv, dep, queue, qtry, tries, resid, q_cnt, dc, a_ptr = carry
            occupied = queue > 0.0
            qmin = jnp.min(jnp.where(occupied, queue, jnp.inf))
            fits = freed & (resid >= qmin)

            # BF-S candidate: largest fitting job for the lowest-index
            # freed server that still has one.
            cur = jnp.min(jnp.where(fits, l_iota, L))
            any_bfs = cur < L
            cur = jnp.minimum(cur, L - 1)
            fitq = jnp.where(occupied & (queue <= resid[cur]), queue,
                             -jnp.inf)
            size_bfs = jnp.max(fitq)
            j_bfs = jnp.min(jnp.where(fitq == size_bfs, q_iota, Qcap))
            j_bfs = jnp.minimum(j_bfs, Qcap - 1)

            # BF-J candidate: next landed arrival (one attempt each, in
            # arrival order, even if BF-S already consumed its job).
            is_bfj = (~any_bfs) & (a_ptr < n_landed)
            ap = jnp.minimum(a_ptr, A_max - 1)
            pos = pos_list[ap]
            size_bfj = queue[jnp.maximum(pos, 0)]
            feas = resid >= size_bfj
            if faulted:
                feas = feas & up_t
            masked_r = jnp.where(feas, resid, jnp.inf)
            best_r = jnp.min(masked_r)
            s_bfj = jnp.min(jnp.where(masked_r == best_r, l_iota, L))
            s_bfj = jnp.minimum(s_bfj, L - 1)
            ok_bfj = is_bfj & (best_r < jnp.inf) & (size_bfj > 0)

            do = any_bfs | ok_bfj
            tgt = jnp.where(any_bfs, cur, s_bfj)
            qidx = jnp.where(do, jnp.where(any_bfs, j_bfs,
                                           jnp.maximum(pos, 0)), Qcap)
            size = jnp.where(any_bfs, size_bfs, size_bfj)
            dur = jnp.where(any_bfs, durs[jnp.minimum(dc, D - 1)],
                            dur_list[ap])

            row = srv[tgt]
            slot = jnp.min(jnp.where(row == 0.0, k_iota, K))
            slot = jnp.where(slot == K, 0, slot)  # row full: reference
            slot_w = jnp.where(do, slot, K)       # engine overwrites slot 0
            new_row = row.at[slot_w].set(size, mode="drop")
            srv = srv.at[tgt].set(new_row)
            dep = dep.at[tgt].set(
                dep[tgt].at[slot_w].set(t + dur, mode="drop"))
            if faulted:
                # retry count rides with the job: queue slot -> server slot
                tr = qtry[jnp.minimum(qidx, Qcap - 1)]
                tries = tries.at[tgt].set(
                    tries[tgt].at[slot_w].set(tr, mode="drop"))
                qtry = qtry.at[qidx].set(0, mode="drop")
            queue = queue.at[qidx].set(0.0, mode="drop")
            resid = resid.at[jnp.where(do, tgt, L)].set(
                1.0 - slot_sum(new_row)[0], mode="drop")
            q_cnt = q_cnt - do.astype(jnp.int32)
            dc = dc + any_bfs.astype(jnp.int32)
            a_ptr = a_ptr + is_bfj.astype(jnp.int32)
            return srv, dep, queue, qtry, tries, resid, q_cnt, dc, a_ptr

        zero = jnp.zeros((), jnp.int32)
        carry = (srv, dep, queue, qtry, tries, resid, q_cnt, zero, zero)
        for _ in range(W):
            carry = work(carry)
        srv, dep, queue, qtry, tries, resid, q_cnt, _, a_ptr = carry

        # saturation check: a placement the reference engine would have made
        # is still possible => the bounded list diverged this slot.  (Missed
        # BF-J attempts whose job was already consumed, or whose job fits no
        # server, are no-ops in the reference engine too — not divergence.)
        qmin = jnp.min(jnp.where(queue > 0.0, queue, jnp.inf))
        pend_bfs = (freed & (resid >= qmin)).any()
        left = (a_iota >= a_ptr) & (a_iota < n_landed)
        sz_left = queue[jnp.maximum(pos_list, 0)]
        cap_max = jnp.max(jnp.where(up_t, resid, -jnp.inf)) if faulted \
            else resid.max()
        pend_bfj = (left & (sz_left > 0) & (sz_left <= cap_max)).any()
        trunc = trunc + (pend_bfs | pend_bfj).astype(jnp.int32)

        out = (q_cnt, server_sum(slot_sum(srv))[0, 0],
               n_dep.astype(jnp.int32))
        return (srv, dep, queue, t + 1, q_cnt, dropped, trunc,
                qtry, tries, preempted, requeued, lost, up_last), out

    if state is None:
        zero = jnp.zeros((), jnp.int32)
        state = (
            jnp.zeros((L, K), jnp.float32),
            jnp.full((L, K), INF_SLOT, jnp.int32),
            jnp.zeros(Qcap, jnp.float32),
            zero,                          # t
            zero,                          # q_cnt
            zero,                          # dropped
            zero,                          # trunc
            jnp.zeros(Qcap, jnp.int32),    # qtry
            jnp.zeros((L, K), jnp.int32),  # tries
            zero,                          # preempted
            zero,                          # requeued
            zero,                          # lost
            jnp.ones((L,), bool),          # up_last
        )
    xs = (streams.n, streams.sizes, streams.durs)
    if faulted:
        xs = xs + (streams.up,)
    state, (qlen, occ, ndep) = jax.lax.scan(slot_step, state, xs)
    res = PolicyResult(qlen, occ, jnp.cumsum(ndep), state[5], state[6],
                       state[9], state[10], state[11])
    return (res, state) if return_state else res


@functools.partial(
    jax.jit,
    static_argnames=("lam", "mu", "sampler", "L", "K", "Qcap", "A_max",
                     "horizon", "fault_rate", "repair_rate", "max_requeue"),
)
def _run_bfjs_reference(key: jax.Array,
                        lam: float,
                        mu: float,
                        sampler: Callable[[jax.Array, int], jax.Array],
                        L: int = 8,
                        K: int = 16,
                        Qcap: int = 512,
                        A_max: int = 8,
                        horizon: int = 10_000,
                        fault_rate: float = 0.0,
                        repair_rate: float = 1.0,
                        max_requeue: int = DEFAULT_MAX_REQUEUE
                        ) -> PolicyResult:
    """The original nested fori/while/cond slot engine (behavioural oracle).

    Serial and branch-heavy — kept verbatim for equivalence testing and as
    the baseline of benchmarks/sched_micro.py.  It draws each slot's
    randomness in-loop from ``key``, on the chain ``make_streams`` replays.

    ``fault_rate > 0`` runs the fault-injected variant: the oracle
    regenerates the exact ``make_fault_plane`` the scan engine's streams
    carry (same key, same fold) and applies the shared ``_preempt_grid``
    eviction between departures and arrivals, so faulted trajectories stay
    bit-matched engine-to-engine.
    """
    def draw(key, _):
        key, _, k_n, k_sizes, k_dur = jax.random.split(key, 5)
        return (key, jnp.minimum(jax.random.poisson(k_n, lam), A_max),
                sampler(k_sizes, A_max),
                _geometric(k_dur, mu, (L * K + A_max,)))

    xs = {"t": jnp.arange(horizon, dtype=jnp.int32)}
    if fault_rate > 0.0:
        xs["up"] = make_fault_plane(key, L=L, horizon=horizon,
                                    fault_rate=fault_rate,
                                    repair_rate=repair_rate)
    return _bfjs_reference(draw, key, xs, L=L, K=K, Qcap=Qcap, A_max=A_max,
                           max_requeue=max_requeue)


def _run_bfjs_reference_streams(streams: SchedStreams, *, L: int, K: int,
                                Qcap: int, A_max: int,
                                max_requeue: int = DEFAULT_MAX_REQUEUE
                                ) -> PolicyResult:
    """The same oracle on pre-drawn ``make_streams`` streams: slot t reads
    ``n[t]``, ``sizes[t]``, ``durs[t]`` (and the fault plane) instead of
    drawing them, so it shares the scan engine's inputs exactly — on a TPU
    the in-loop draws can round a rare duration differently from the
    batched ``make_streams`` draws."""
    _check_sequential_durs(streams, L, K, A_max)
    xs = {"t": jnp.arange(streams.n.shape[0], dtype=jnp.int32),
          "n": streams.n, "sizes": streams.sizes, "durs": streams.durs}
    if streams.up is not None:
        xs["up"] = streams.up
    return _bfjs_reference(
        lambda key, x: (key, x["n"], x["sizes"], x["durs"]),
        jax.random.PRNGKey(0), xs, L=L, K=K, Qcap=Qcap, A_max=A_max,
        max_requeue=max_requeue)


def _bfjs_reference(draw, key, xs, *, L, K, Qcap, A_max, max_requeue):
    """The oracle's slot loop.  ``xs`` holds the per-slot inputs (``t``,
    the fault plane ``up`` when faulted, and whatever ``draw`` reads);
    ``draw(key, x) -> (key, n, sizes, durs)`` supplies slot x's arrivals."""
    from .ops import best_fit_server, largest_fitting_job

    faulted = "up" in xs

    def place_in_server(srv_i, dep_i, size, dslot):
        slot = jnp.argmax(srv_i == 0.0)
        return srv_i.at[slot].set(size), dep_i.at[slot].set(dslot), slot

    def slot_step(state: BFJSState, x):
        (srv, dep, queue, dropped, key, qtry, tries,
         preempted, requeued, lost, up_last) = state
        t, up_t = x["t"], x.get("up")
        key, n, sizes, durs = draw(key, x)

        # 1. departures
        leaving = dep == t
        freed = leaving.any(axis=1)
        n_dep = leaving.sum()
        srv = jnp.where(leaving, 0.0, srv)
        dep = jnp.where(leaving, INF_SLOT, dep)

        # 1b. capacity shocks (identical rule to the scan engine: the
        # shared _preempt_grid, then recovered servers count as freed and
        # down servers leave every feasibility mask).
        if faulted:
            tries = jnp.where(leaving, 0, tries)
            srv, dep, tries, queue, qtry, n_p, n_r, n_l = _preempt_grid(
                srv, dep, tries, queue, qtry, up_t, max_requeue)
            preempted = preempted + n_p
            requeued = requeued + n_r
            lost = lost + n_l
            freed = (freed | (up_t & ~up_last)) & up_t
            up_last = up_t

        # 2. arrivals -> queue (record the slots they landed in)
        valid = jnp.arange(A_max) < n
        empty_slots = jnp.nonzero(queue == 0.0, size=A_max, fill_value=Qcap)[0]
        landed = valid & (empty_slots < Qcap)
        dropped = dropped + (valid & ~landed).sum()
        queue = queue.at[jnp.where(landed, empty_slots, Qcap)].set(
            jnp.where(landed, sizes, 0.0), mode="drop")
        new_pos = jnp.where(landed, empty_slots, -1)

        dcounter = 0

        # 3. BF-S over freed servers: fill each with the largest fitting job.
        def bfs_server(i, carry):
            srv, dep, queue, qtry, tries, dc = carry

            def try_place(carry):
                srv, dep, queue, qtry, tries, dc, go = carry
                resid = 1.0 - slot_sum(srv[i])[0]
                j = largest_fitting_job(queue, resid)
                ok = j >= 0

                def do(args):
                    srv, dep, queue, qtry, tries, dc = args
                    size = queue[j]
                    s_i, d_i, slot = place_in_server(srv[i], dep[i], size,
                                                     t + durs[dc])
                    if faulted:
                        tries = tries.at[i, slot].set(qtry[j])
                        qtry = qtry.at[j].set(0)
                    return (srv.at[i].set(s_i), dep.at[i].set(d_i),
                            queue.at[j].set(0.0), qtry, tries, dc + 1)

                srv, dep, queue, qtry, tries, dc = jax.lax.cond(
                    ok, do, lambda a: a, (srv, dep, queue, qtry, tries, dc))
                return srv, dep, queue, qtry, tries, dc, ok

            def fill(carry):
                srv, dep, queue, qtry, tries, dc = carry
                out = jax.lax.while_loop(
                    lambda c: c[6],
                    try_place,
                    (srv, dep, queue, qtry, tries, dc, True))
                return out[:6]

            return jax.lax.cond(freed[i], fill, lambda c: c,
                                (srv, dep, queue, qtry, tries, dc))

        srv, dep, queue, qtry, tries, dcounter = jax.lax.fori_loop(
            0, L, bfs_server, (srv, dep, queue, qtry, tries, dcounter))

        # 4. BF-J over the new arrivals still in queue.
        def bfj_job(a, carry):
            srv, dep, queue, qtry, tries, dc = carry
            pos = new_pos[a]
            size = jnp.where(pos >= 0, queue[jnp.maximum(pos, 0)], 0.0)
            resid = 1.0 - slot_sum(srv)[:, 0]
            if faulted:
                resid = jnp.where(up_t, resid, -jnp.inf)
            s_idx = best_fit_server(resid, jnp.where(size > 0, size, jnp.inf))
            ok = (size > 0) & (s_idx >= 0)

            def do(args):
                srv, dep, queue, qtry, tries, dc = args
                s_i, d_i, slot = place_in_server(srv[s_idx], dep[s_idx], size,
                                                 t + durs[L * K + a])
                if faulted:
                    tries = tries.at[s_idx, slot].set(qtry[jnp.maximum(pos, 0)])
                    qtry = qtry.at[jnp.maximum(pos, 0)].set(0)
                return (srv.at[s_idx].set(s_i), dep.at[s_idx].set(d_i),
                        queue.at[pos].set(0.0), qtry, tries, dc)

            return jax.lax.cond(ok, do, lambda x: x,
                                (srv, dep, queue, qtry, tries, dc))

        srv, dep, queue, qtry, tries, dcounter = jax.lax.fori_loop(
            0, A_max, bfj_job, (srv, dep, queue, qtry, tries, dcounter))

        out = (
            (queue > 0).sum().astype(jnp.int32),
            server_sum(slot_sum(srv))[0, 0],
            n_dep.astype(jnp.int32),
        )
        return BFJSState(srv, dep, queue, dropped, key, qtry, tries,
                         preempted, requeued, lost, up_last), out

    zero = jnp.zeros((), jnp.int32)
    state0 = BFJSState(
        srv=jnp.zeros((L, K), jnp.float32),
        dep=jnp.full((L, K), INF_SLOT, jnp.int32),
        queue=jnp.zeros(Qcap, jnp.float32),
        dropped=zero,
        key=key,
        qtry=jnp.zeros(Qcap, jnp.int32),
        tries=jnp.zeros((L, K), jnp.int32),
        preempted=zero,
        requeued=zero,
        lost=zero,
        up_last=jnp.ones((L,), bool),
    )
    state, (qlen, occ, ndep) = jax.lax.scan(slot_step, state0, xs)
    return PolicyResult(qlen, occ, jnp.cumsum(ndep), state.dropped,
                        jnp.zeros((), jnp.int32), state.preempted,
                        state.requeued, state.lost)


def run_bfjs(key: jax.Array,
             lam: float,
             mu: float,
             sampler: Callable[[jax.Array, int], jax.Array],
             L: int = 8,
             K: int = 16,
             Qcap: int = 512,
             A_max: int = 8,
             horizon: int = 10_000,
             engine: str = "scan",
             work_steps: int | None = None,
             window: int | None = None,
             fault_rate: float = 0.0,
             repair_rate: float = 1.0,
             max_requeue: int = DEFAULT_MAX_REQUEUE) -> PolicyResult:
    """Simulate BF-J/S on L unit-capacity servers for `horizon` slots.

    sampler(key, n) -> (n,) float sizes in (0,1].  vmap over `key` for
    Monte-Carlo ensembles (or use monte_carlo_bfjs, which also knows the
    gridded Pallas engine).

    engine: "scan" (branch-free, default) | "reference" (original nested
    loop oracle) | "pallas" (fused kernels/bfjs slot-step kernel).

    ``fault_rate > 0`` injects per-slot server capacity shocks
    (``make_fault_plane``): down servers evict their jobs, which requeue up
    to ``max_requeue`` times and are counted ``lost`` past that — reported
    in the result's ``preempted/requeued/lost`` counters, identically on
    every engine.
    """
    if engine == "reference":
        return _run_bfjs_reference(key, lam, mu, sampler, L=L, K=K, Qcap=Qcap,
                                   A_max=A_max, horizon=horizon,
                                   fault_rate=fault_rate,
                                   repair_rate=repair_rate,
                                   max_requeue=max_requeue)
    streams = make_streams(key, lam, mu, sampler, L=L, K=K, A_max=A_max,
                           horizon=horizon, fault_rate=fault_rate,
                           repair_rate=repair_rate)
    return run_bfjs_trace(streams, L=L, K=K, Qcap=Qcap, A_max=A_max,
                          engine=engine, work_steps=work_steps,
                          window=window, max_requeue=max_requeue)


def run_bfjs_trace(streams: SchedStreams, *, L: int, K: int, Qcap: int,
                   A_max: int, engine: str = "scan",
                   work_steps: int | None = None,
                   window: int | None = None,
                   max_requeue: int = DEFAULT_MAX_REQUEUE,
                   strict: bool = False) -> PolicyResult:
    """Run one BF-J/S simulation over explicit streams (make_streams-shaped;
    trace-built streams are rejected — see _check_sequential_durs).
    ``window`` is the Pallas kernel's VMEM time-window length (must divide
    the horizon; ignored by the other engines)."""
    _check_sequential_durs(streams, L, K, A_max)
    if engine == "reference":
        return _run_bfjs_reference_streams(streams, L=L, K=K, Qcap=Qcap,
                                           A_max=A_max,
                                           max_requeue=max_requeue)
    if engine == "scan":
        return run_bfjs_streams(streams, L=L, K=K, Qcap=Qcap, A_max=A_max,
                                work_steps=work_steps,
                                max_requeue=max_requeue)
    if engine == "pallas":
        from repro.kernels.bfjs.ops import bfjs_simulate, bfjs_vmem_bytes
        from repro.kernels.common import (ensemble_plane_bytes,
                                          pallas_precheck, resolve_windows)
        T, D = streams.n.shape[0], streams.durs.shape[-1]
        if not pallas_precheck(
                "bfjs", nbytes=bfjs_vmem_bytes(
                    L, K, Qcap, A_max, resolve_windows(T, window)[0]),
                hbm_bytes=ensemble_plane_bytes(
                    1, T, stream_lanes=1 + A_max + D, out_lanes=3),
                fault_plane=streams.up is not None, strict=strict):
            return run_bfjs_streams(streams, L=L, K=K, Qcap=Qcap,
                                    A_max=A_max, work_steps=work_steps,
                                    max_requeue=max_requeue)
        batched = jax.tree.map(lambda x: x[None], streams)
        res = bfjs_simulate(batched, L=L, K=K, Qcap=Qcap, A_max=A_max,
                            work_steps=work_steps, window=window)
        return jax.tree.map(lambda x: x[0], res)
    raise ValueError(f"unknown engine {engine!r}")


def run_bfjs_workload(workload, key: jax.Array, *, engine: str = "scan",
                      **config) -> PolicyResult:
    """Workload-first adapter: the registry entry behind
    ``run_policy(workload, policy="bfjs", ...)``.  BF-J/S is
    single-resource with unit servers; vector workloads are rejected
    loudly (use ``policy="bfjs-mr"``)."""
    workload.require_scalar("bfjs")
    workload.check_sampler()
    return run_bfjs(key, workload.lam, workload.mu, workload.sampler,
                    engine=engine, **config)


def monte_carlo_bfjs_workload(workload, keys: jax.Array, *,
                              engine: str = "scan", **config) -> PolicyResult:
    """Workload-first adapter for ``monte_carlo_policy(policy="bfjs")``."""
    workload.require_scalar("bfjs")
    workload.check_sampler()
    return monte_carlo_bfjs(keys, workload.lam, workload.mu,
                            workload.sampler, engine=engine, **config)


def monte_carlo_bfjs(keys: jax.Array, lam: float, mu: float, sampler,
                     engine: str = "scan", work_steps: int | None = None,
                     window: int | None = None,
                     L: int = 8, K: int = 16, Qcap: int = 512,
                     A_max: int = 8, horizon: int = 10_000,
                     fault_rate: float = 0.0, repair_rate: float = 1.0,
                     max_requeue: int = DEFAULT_MAX_REQUEUE,
                     strict: bool = False) -> PolicyResult:
    """One simulated cluster per key.

    "scan"/"reference" vmap run_bfjs over the keys; "pallas" pre-generates
    every ensemble member's streams and runs the fused kernel with the
    ensemble as the kernel grid (one independent cluster per program
    instance)."""
    if engine == "pallas":
        from repro.kernels.bfjs.ops import bfjs_simulate, bfjs_vmem_bytes
        from repro.kernels.common import (ensemble_plane_bytes,
                                          pallas_precheck, resolve_windows)
        # keys is the LOCAL batch here: under a sharded mesh launch
        # (core.engine.sharding) each device traces with its G/D shard, so
        # this footprint check is naturally per device.
        G = int(keys.shape[0])
        if not pallas_precheck(
                "bfjs", nbytes=bfjs_vmem_bytes(
                    L, K, Qcap, A_max, resolve_windows(horizon, window)[0]),
                hbm_bytes=ensemble_plane_bytes(
                    G, horizon, stream_lanes=1 + A_max + (L * K + A_max),
                    out_lanes=3),
                fault_plane=fault_rate > 0.0, strict=strict):
            engine = "scan"
        else:
            streams = jax.vmap(
                lambda k: make_streams(k, lam, mu, sampler, L=L, K=K,
                                       A_max=A_max, horizon=horizon))(keys)
            return bfjs_simulate(streams, L=L, K=K, Qcap=Qcap, A_max=A_max,
                                 work_steps=work_steps, window=window)
    fn = functools.partial(run_bfjs, lam=lam, mu=mu, sampler=sampler,
                           engine=engine, work_steps=work_steps, L=L, K=K,
                           Qcap=Qcap, A_max=A_max, horizon=horizon,
                           fault_rate=fault_rate, repair_rate=repair_rate,
                           max_requeue=max_requeue)
    return jax.vmap(fn)(keys)
