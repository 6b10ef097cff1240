"""Multi-resource BF-J/S engines (paper Section VIII) on the scan stack.

Ports ``core/multi_resource.py``'s Tetris-alignment BF-J/S — the paper's
named future-work extension, cf. Yao et al. (*Throughput-Optimal
Multiresource-Job Scheduling*) — onto the fixed-shape accelerator stack as
``policy="bfjs-mr"``:

  * ``engine="reference"`` — the event-driven ``MultiResourceBFJS`` numpy
    oracle driven slot-by-slot from the same ``SchedStreams`` (host-side,
    not jittable): the behavioural anchor;
  * ``engine="scan"``      — a branch-free ``lax.scan`` over slots with a
    bounded early-exit placement work list, the same program shape as the
    single-resource BF-J/S scan engine, generalized to ``(L, R)`` integer
    occupancy and capacity planes (each server its own capacity) and
    ``(Qcap, R)`` queued demand vectors;
  * ``engine="pallas"``    — the fused slot-step kernel in
    ``kernels/bfjs_mr`` (occupancy planes, queue state and counters stay
    resident in VMEM; the Monte-Carlo ensemble is the kernel grid), which
    bit-matches "scan" whenever ``truncated == 0``.

Semantics (one slot, identical to the oracle's ``step``):

  1. departures free their demand vectors;
  2. arrivals join the queue (first-empty positions, arrival-order seq ids);
  3. BF-S over freed servers in ascending order: repeatedly place the
     queued job with the LARGEST total demand that fits (ties: lowest seq,
     i.e. earliest arrival — the oracle's insertion-order tie-break);
  4. BF-J over the slot's arrivals in order: place each still-queued job on
     the feasible server with the LOWEST alignment score
     ``<demand, available>`` (ties: lowest server index).

Exactness: demands and occupancies are ``quantize.RES`` grid integers, so
every feasibility and total-demand comparison is exact; the alignment
score is exact integer arithmetic compared as an int32 ``(hi, lo)`` pair
(``alignment_score_pair_jnp``), equal to the oracle's exact float64
``alignment_scores`` on every backend, vmap batch width and compiler
version — so ``"scan"`` bit-matches ``"reference"`` whenever
``truncated == 0``, and sharded/unsharded runs bit-match each other.

Durations attach to jobs at arrival (like VQS), so trace-built streams
(``streams_from_trace(trace, collapse=False)`` — per-arrival duration
lanes only) replay directly: the path that runs the synthesized Google-like
(cpu, mem) trace uncollapsed, the preprocessing step the paper's Section
VIII wants removed.

Fixed-shape deviations (counted, never silent): queue overflow beyond
``Qcap`` drops arrivals (``dropped``); a placement onto a server whose
``K`` job slots are full is skipped and counted (``truncated``), as are
slots that exhaust the ``work_steps`` bound with placements still pending.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..quantize import RES
from .bfjs import DEFAULT_MAX_REQUEUE
from .ops import alignment_score_pair_jnp
from .streams import (INF_SLOT, PolicyResult, SchedStreams, make_streams,
                      resolve_work_steps)
from .workload import capacity_plane

INT32_MAX = jnp.iinfo(jnp.int32).max


def _preempt_planes(dem, dep, occ, qdem, qdur, qseq, qtry, tries, sseq,
                    seq0, q_cnt, up_t, t, max_requeue):
    """Evict every job in service on a down server (multi-resource planes).

    Victims with ``tries < max_requeue`` re-enter the queue at the first
    empty positions in ascending current-``seq`` order, carrying their
    REMAINING duration, ``tries + 1`` and a FRESH seq id — exactly the
    oracle's dict-insertion order (requeues before the slot's arrivals),
    so BF-S tie-breaks keep bit-matching.  Exhausted victims (and any that
    find the queue full) are dropped entirely and counted ``lost``.
    Returns the updated planes plus ``(n_preempted, n_requeued, n_lost)``.
    """
    R = dem.shape[-1]
    victim = (~up_t)[:, None] & (dep != INF_SLOT)
    vic_f = victim.reshape(-1)
    elig = vic_f & (tries.reshape(-1) < max_requeue)
    # rank eligible victims by current seq; ineligible sort to the back
    key = jnp.where(elig, sseq.reshape(-1), INT32_MAX)
    rank_of = jnp.argsort(jnp.argsort(key)).astype(jnp.int32)
    n_empty = jnp.cumsum((qseq < 0).astype(jnp.int32))
    pos = jnp.searchsorted(n_empty, rank_of + 1)
    land = elig & (pos < qseq.shape[0])
    at = jnp.where(land, pos, qseq.shape[0])
    rem = jnp.maximum(dep.reshape(-1) - t, 1)
    qdem = qdem.at[at].set(dem.reshape(-1, R), mode="drop")
    qdur = qdur.at[at].set(rem, mode="drop")
    qtry = qtry.at[at].set(tries.reshape(-1) + 1, mode="drop")
    qseq = qseq.at[at].set(seq0 + rank_of, mode="drop")
    n_vict = vic_f.sum()
    n_req = land.sum()
    seq0 = seq0 + n_req
    q_cnt = q_cnt + n_req
    occ = occ - (dem * victim[..., None]).sum(axis=1)
    dem = jnp.where(victim[..., None], 0, dem)
    dep = jnp.where(victim, INF_SLOT, dep)
    tries = jnp.where(victim, 0, tries)
    sseq = jnp.where(victim, 0, sseq)
    return (dem, dep, occ, qdem, qdur, qseq, qtry, tries, sseq, seq0,
            q_cnt, n_vict, n_req, n_vict - n_req)


def _lift_sizes(streams: SchedStreams) -> SchedStreams:
    """bfjs-mr consumes (T, A_max, R) sizes; lift squeezed R=1 streams."""
    if streams.sizes.ndim == streams.durs.ndim:
        return streams._replace(sizes=streams.sizes[..., None])
    return streams


def run_bfjs_mr_streams(streams: SchedStreams, L: int, K: int, Qcap: int,
                        A_max: int, work_steps: int | None = None,
                        capacity=1.0,
                        max_requeue: int = DEFAULT_MAX_REQUEUE,
                        state: tuple | None = None,
                        return_state: bool = False):
    """Branch-free multi-resource BF-J/S slot engine over streams.

    ``capacity`` is a scalar, a length-R tuple or an ``(L, R)`` per-server
    plane; it enters the program as the ``capacity_plane`` grid integers.
    The result's ``steps`` counts the work steps run and
    ``bfs_placements`` the placements BF-S refills made, both summed over
    the slots (and carried in ``state`` across chunks).

    One ``lax.scan`` over slots; inside each slot the BF-S refill and BF-J
    placement passes are a bounded early-exit work list
    (``lax.while_loop`` capped at ``work_steps``).  Each step either
    performs the BF-S placement for the lowest-index freed server that
    still has a fitting queued job, or attempts the next arrival's BF-J
    placement — the same dynamic dispatch as the single-resource engine,
    with vector feasibility (``all_r  dem_r <= avail_r``) and the exact
    integer alignment-score pair replacing scalar residual comparisons.  Placements
    only consume queue entries and only shrink availability, so the
    lowest-index-first order reproduces the oracle's nested loops exactly.
    """
    streams = _lift_sizes(streams)
    cap = capacity_plane(capacity, L, int(streams.sizes.shape[-1]))
    return _run_bfjs_mr_streams(streams, jnp.asarray(cap), L=L, K=K,
                                Qcap=Qcap, A_max=A_max,
                                work_steps=work_steps,
                                max_requeue=max_requeue, state=state,
                                return_state=return_state)


@functools.partial(
    jax.jit,
    static_argnames=("L", "K", "Qcap", "A_max", "work_steps",
                     "max_requeue", "return_state"))
def _run_bfjs_mr_streams(streams: SchedStreams, cap: jax.Array, L: int,
                         K: int, Qcap: int, A_max: int,
                         work_steps: int | None, max_requeue: int,
                         state: tuple | None, return_state: bool):
    """``run_bfjs_mr_streams`` on the ``(L, R)`` int32 capacity plane."""
    horizon, _, R = streams.sizes.shape
    W = resolve_work_steps(work_steps, A_max)
    faulted = streams.up is not None
    a_iota = jnp.arange(A_max)
    l_iota = jnp.arange(L)
    q_iota = jnp.arange(Qcap)
    k_iota = jnp.arange(K)
    dur_off = streams.durs.shape[-1] - A_max

    def slot_step(state, inp):
        (dem, dep, occ, qdem, qdur, qseq, t, q_cnt, seq0, dropped, trunc,
         qtry, tries, sseq, preempted, requeued, lost, up_last, steps,
         bfs) = state
        if faulted:
            n, sizes, durs, up_t = inp
        else:
            n, sizes, durs = inp
            up_t = None

        # 1. departures
        leaving = dep == t
        freed = leaving.any(axis=1)
        n_dep = leaving.sum()
        occ = occ - (dem * leaving[..., None]).sum(axis=1)
        dem = jnp.where(leaving[..., None], 0, dem)
        dep = jnp.where(leaving, INF_SLOT, dep)
        tries = jnp.where(leaving, 0, tries)
        sseq = jnp.where(leaving, 0, sseq)

        # 1b. fault preemption: down servers evict, victims requeue or
        # are lost; recovered servers rejoin the BF-S freed set.
        if faulted:
            (dem, dep, occ, qdem, qdur, qseq, qtry, tries, sseq, seq0,
             q_cnt, n_v, n_r, n_l) = _preempt_planes(
                 dem, dep, occ, qdem, qdur, qseq, qtry, tries, sseq,
                 seq0, q_cnt, up_t, t, max_requeue)
            preempted = preempted + n_v
            requeued = requeued + n_r
            lost = lost + n_l
            freed = (freed | (up_t & ~up_last)) & up_t
            up_last = up_t

        # 2. arrivals -> first empty queue positions (grid-quantized)
        g = jnp.maximum(jnp.round(sizes * RES), 1.0).astype(jnp.int32)
        n_empty = jnp.cumsum((qseq < 0).astype(jnp.int32))
        pos_a = jnp.searchsorted(n_empty, a_iota + 1)
        landed = (a_iota < n) & (pos_a < Qcap)
        n_landed = landed.sum()
        dropped = dropped + n - n_landed
        q_cnt = q_cnt + n_landed
        wpos = jnp.where(landed, pos_a, Qcap)
        qdem = qdem.at[wpos].set(jnp.where(landed[:, None], g, 0),
                                 mode="drop")
        qdur = qdur.at[wpos].set(durs[dur_off + a_iota], mode="drop")
        qseq = qseq.at[wpos].set(seq0 + a_iota, mode="drop")
        qtry = qtry.at[wpos].set(0, mode="drop")
        seq0 = seq0 + n
        new_pos = jnp.where(landed, pos_a, -1)
        rank = jnp.cumsum(landed.astype(jnp.int32)) - 1
        landed_list = jnp.full((A_max,), A_max - 1, jnp.int32).at[
            jnp.where(landed, rank, A_max)].set(a_iota.astype(jnp.int32),
                                                mode="drop")
        pos_list = new_pos[landed_list]

        def fits_matrix(occ, qdem, qseq, freed_mask):
            """(L, Qcap) — job j fits on server i (static unroll over R)."""
            avail = cap - occ
            fits = freed_mask[:, None] & (qseq >= 0)[None, :]
            for r in range(R):
                fits = fits & (qdem[:, r][None, :] <= avail[:, r][:, None])
            return fits

        # 3+4. BF-S then BF-J as one bounded early-exit work list
        def work(carry):
            (dem, dep, occ, qdem, qdur, qseq, qtry, tries, sseq, q_cnt,
             blocked, a_ptr, trunc, bfs, done, n_steps) = carry
            avail = cap - occ

            # BF-S candidate: lowest-index freed, unblocked server with a
            # fitting job; its job = largest total demand, earliest seq.
            fits = fits_matrix(occ, qdem, qseq, freed & ~blocked)
            has_fit = fits.any(axis=1)
            cur = jnp.min(jnp.where(has_fit, l_iota, L))
            any_bfs = cur < L
            cur_c = jnp.minimum(cur, L - 1)
            fit_cur = fits[cur_c]
            tot = qdem.sum(axis=-1)
            best_tot = jnp.max(jnp.where(fit_cur, tot, -1))
            cand = fit_cur & (tot == best_tot)
            best_seq = jnp.min(jnp.where(cand, qseq, INT32_MAX))
            j_bfs = jnp.min(jnp.where(cand & (qseq == best_seq), q_iota,
                                      Qcap))
            j_bfs = jnp.minimum(j_bfs, Qcap - 1)

            # BF-J candidate: next landed arrival still in the queue, on
            # the min-alignment feasible server (any server, not just
            # freed — the oracle's _best_server scans all L).
            is_bfj = (~any_bfs) & (a_ptr < n_landed)
            ap = jnp.minimum(a_ptr, A_max - 1)
            pos = pos_list[ap]
            posc = jnp.maximum(pos, 0)
            present = is_bfj & (pos >= 0) & (qseq[posc] >= 0)
            d_bfj = qdem[posc]
            feas = jnp.ones((L,), bool)
            for r in range(R):
                feas = feas & (d_bfj[r] <= avail[:, r])
            if faulted:
                feas = feas & up_t
            s_hi, s_lo = alignment_score_pair_jnp(avail, d_bfj)
            best_hi = jnp.min(jnp.where(feas, s_hi, INT32_MAX))
            cand_j = feas & (s_hi == best_hi)
            best_lo = jnp.min(jnp.where(cand_j, s_lo, INT32_MAX))
            s_bfj = jnp.min(jnp.where(cand_j & (s_lo == best_lo), l_iota,
                                      L))
            s_bfj_c = jnp.minimum(s_bfj, L - 1)
            ok_bfj = present & feas.any()

            do = any_bfs | ok_bfj
            tgt = jnp.where(any_bfs, cur_c, s_bfj_c)
            qidx = jnp.where(any_bfs, j_bfs, posc)
            d_place = qdem[qidx]
            dur = qdur[qidx]
            try_pl = qtry[qidx]
            seq_pl = qseq[qidx]

            row_dep = dep[tgt]
            slot = jnp.min(jnp.where(row_dep == INF_SLOT, k_iota, K))
            ok_slot = slot < K
            place = do & ok_slot
            slot_w = jnp.where(place, jnp.minimum(slot, K - 1), K)
            dem = dem.at[tgt, slot_w].set(d_place, mode="drop")
            dep = dep.at[tgt, slot_w].set(t + dur, mode="drop")
            tries = tries.at[tgt, slot_w].set(try_pl, mode="drop")
            sseq = sseq.at[tgt, slot_w].set(seq_pl, mode="drop")
            occ = occ.at[jnp.where(place, tgt, L)].add(d_place, mode="drop")
            qclear = jnp.where(place, qidx, Qcap)
            qseq = qseq.at[qclear].set(-1, mode="drop")
            qdem = qdem.at[qclear].set(0, mode="drop")
            qtry = qtry.at[qclear].set(0, mode="drop")
            q_cnt = q_cnt - place.astype(jnp.int32)
            bfs = bfs + (place & any_bfs).astype(jnp.int32)
            # K-full server: the oracle would place; count, don't spin.
            trunc = trunc + (do & ~ok_slot).astype(jnp.int32)
            blocked = blocked | (any_bfs & ~ok_slot)
            a_ptr = a_ptr + is_bfj.astype(jnp.int32)
            # BF-S fits only shrink and each arrival is attempted once, so
            # once neither exists the slot is finished for good.
            done = (~any_bfs) & (a_ptr >= n_landed)
            return (dem, dep, occ, qdem, qdur, qseq, qtry, tries, sseq,
                    q_cnt, blocked, a_ptr, trunc, bfs, done, n_steps + 1)

        def unfinished(carry):
            done, n_steps = carry[14], carry[15]
            return (~done) & (n_steps < W)

        zero = jnp.zeros((), jnp.int32)
        carry = (dem, dep, occ, qdem, qdur, qseq, qtry, tries, sseq,
                 q_cnt, jnp.zeros((L,), bool), zero, trunc, bfs,
                 jnp.zeros((), bool), zero)
        carry = jax.lax.while_loop(unfinished, work, carry)
        (dem, dep, occ, qdem, qdur, qseq, qtry, tries, sseq, q_cnt,
         blocked, a_ptr, trunc, bfs, done, n_steps) = carry
        steps = steps + n_steps

        # saturation check: work the oracle would still do => the bounded
        # list diverged this slot (K-full blocks were already counted).
        fits = fits_matrix(occ, qdem, qseq, freed & ~blocked)
        pend_bfs = fits.any()
        left = (a_iota >= a_ptr) & (a_iota < n_landed)
        posb = jnp.maximum(pos_list, 0)
        present_l = left & (pos_list >= 0) & (qseq[posb] >= 0)
        avail = cap - occ
        feas_l = jnp.ones((A_max, L), bool)
        for r in range(R):
            feas_l = feas_l & (qdem[posb][:, r][:, None]
                               <= avail[:, r][None, :])
        if faulted:
            feas_l = feas_l & up_t[None, :]
        pend_bfj = (present_l & feas_l.any(axis=1)).any()
        trunc = trunc + (pend_bfs | pend_bfj).astype(jnp.int32)

        out = (q_cnt, occ.sum(axis=0).astype(jnp.float32) / RES,
               n_dep.astype(jnp.int32))
        state = (dem, dep, occ, qdem, qdur, qseq, t + 1, q_cnt, seq0,
                 dropped, trunc, qtry, tries, sseq, preempted, requeued,
                 lost, up_last, steps, bfs)
        return state, out

    zero = jnp.zeros((), jnp.int32)
    if state is None:
        state = (
            jnp.zeros((L, K, R), jnp.int32),
            jnp.full((L, K), INF_SLOT, jnp.int32),
            jnp.zeros((L, R), jnp.int32),
            jnp.zeros((Qcap, R), jnp.int32),
            jnp.ones((Qcap,), jnp.int32),
            jnp.full((Qcap,), -1, jnp.int32),
            zero, zero, zero, zero, zero,
            jnp.zeros((Qcap,), jnp.int32),   # qtry: queued retry counts
            jnp.zeros((L, K), jnp.int32),    # tries: in-service retries
            jnp.zeros((L, K), jnp.int32),    # sseq: in-service seq ids
            zero, zero, zero,                # preempted / requeued / lost
            jnp.ones((L,), bool),            # up_last (recovery detection)
            zero, zero,                      # steps / bfs_placements
        )
    xs = (streams.n, streams.sizes, streams.durs)
    if faulted:
        xs = xs + (streams.up,)
    state, (qlen, occ, ndep) = jax.lax.scan(slot_step, state, xs)
    res = PolicyResult(qlen, occ, jnp.cumsum(ndep), state[9], state[10],
                       state[14], state[15], state[16], steps=state[18],
                       bfs_placements=state[19])
    return (res, state) if return_state else res


def _run_bfjs_mr_reference(streams: SchedStreams, *, L: int,
                           capacity=1.0,
                           max_requeue: int = DEFAULT_MAX_REQUEUE
                           ) -> PolicyResult:
    """The event-driven ``MultiResourceBFJS`` oracle driven from streams.

    Host-side numpy, slot by slot — not jittable, kept as the behavioural
    anchor the scan engine is parity-tested against.  Demands are the same
    grid quantization the scan engine applies (``max(round(s * RES), 1)``)
    replayed as exact dyadics ``g / RES``; the capacity plane is the
    engines' ``capacity_plane`` over ``RES``, so every feasibility
    comparison is exact and agrees with the integer engine.  When the
    streams carry a fault plane the oracle is stepped with ``down =
    ~up[t]`` and the counters come from its fault accounting (lost jobs never depart, so cumulative departures subtract
    them).  The oracle has no fixed-size buffers: ``dropped`` and
    ``truncated`` are always 0.
    """
    from ..multi_resource import MRJob, MultiResourceBFJS

    streams = _lift_sizes(streams)
    n = np.asarray(streams.n)
    sizes = np.asarray(streams.sizes, dtype=np.float64)
    durs = np.asarray(streams.durs)
    up = None if streams.up is None else np.asarray(streams.up)
    T, A_max, R = sizes.shape
    cap_dyadic = capacity_plane(capacity, L, R) / RES
    g = np.maximum(np.rint(sizes * RES), 1.0)
    dem = g / RES
    dur_off = durs.shape[-1] - A_max

    policy = MultiResourceBFJS(L, R, capacity=cap_dyadic)
    qlen = np.zeros(T, dtype=np.int32)
    occ = np.zeros((T, R), dtype=np.float64)
    dep_cum = np.zeros(T, dtype=np.int32)
    jid = 0
    for t in range(T):
        jobs = []
        for a in range(int(n[t])):
            jobs.append(MRJob(jid, dem[t, a], t, int(durs[t, dur_off + a])))
            jid += 1
        down = None if up is None else ~up[t]
        policy.step(t, jobs, down=down, max_requeue=max_requeue)
        q = policy.queue_len()
        qlen[t] = q
        occ[t] = policy.occupied.sum(axis=0)
        in_service = sum(len(s) for s in policy.jobs)
        dep_cum[t] = jid - in_service - q - policy.lost
    i32 = lambda v: jnp.asarray(np.int32(v))
    return PolicyResult(
        jnp.asarray(qlen), jnp.asarray(occ.astype(np.float32)),
        jnp.asarray(dep_cum), jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.int32), i32(policy.preempted),
        i32(policy.requeued), i32(policy.lost), steps=i32(policy.steps),
        bfs_placements=i32(policy.bfs_placements))


def run_bfjs_mr_trace(streams: SchedStreams, *, L: int, K: int = 16,
                      Qcap: int = 512, A_max: int | None = None,
                      engine: str = "scan", work_steps: int | None = None,
                      capacity=1.0,
                      window: int | None = None,
                      max_requeue: int = DEFAULT_MAX_REQUEUE,
                      strict: bool = False) -> PolicyResult:
    """Run one multi-resource BF-J/S simulation over explicit streams.

    Accepts both trace-built streams (per-arrival duration lanes only —
    the ``streams_from_trace(trace, collapse=False)`` path) and
    ``make_streams`` full-width streams (the engine consumes the last
    ``A_max`` per-arrival lanes; durations attach at arrival).  ``window``
    is the Pallas engine's VMEM time-window length (must divide the
    horizon; ignored by the other engines).  ``capacity`` is a scalar, a
    length-R tuple or an ``(L, R)`` per-server plane.  ``engine="pallas"``
    is gated by :func:`repro.kernels.common.pallas_precheck` — a fault
    plane or an over-budget VMEM estimate degrades to the bit-identical
    scan engine with a :class:`GracefulDegradationWarning` (or raises,
    ``strict=True``).
    """
    streams = _lift_sizes(streams)
    if A_max is None:
        A_max = int(streams.sizes.shape[1])
    if engine == "reference":
        return _run_bfjs_mr_reference(streams, L=L, capacity=capacity,
                                      max_requeue=max_requeue)
    if engine == "pallas":
        from repro.kernels.bfjs_mr.ops import (bfjs_mr_simulate,
                                               bfjs_mr_vmem_bytes)
        from repro.kernels.common import (ensemble_plane_bytes,
                                          pallas_precheck, resolve_windows)
        R = int(streams.sizes.shape[-1])
        T, D = streams.n.shape[0], streams.durs.shape[-1]
        if not pallas_precheck(
                "bfjs-mr", nbytes=bfjs_mr_vmem_bytes(
                    L, K, Qcap, A_max, R, resolve_windows(T, window)[0]),
                hbm_bytes=ensemble_plane_bytes(
                    1, T, stream_lanes=1 + A_max * R + D, out_lanes=2 + R),
                fault_plane=streams.up is not None, strict=strict):
            engine = "scan"
        else:
            batched = jax.tree.map(lambda x: x[None], streams)
            res = bfjs_mr_simulate(batched, L=L, K=K, Qcap=Qcap,
                                   A_max=A_max, work_steps=work_steps,
                                   capacity=capacity, window=window)
            return jax.tree.map(lambda x: x[0], res)
    if engine == "scan":
        return run_bfjs_mr_streams(streams, L=L, K=K, Qcap=Qcap,
                                   A_max=A_max, work_steps=work_steps,
                                   capacity=capacity,
                                   max_requeue=max_requeue)
    raise ValueError(f"unknown engine {engine!r}")


def run_bfjs_mr_workload(workload, key, *, engine: str = "scan",
                         L: int = 8, K: int = 16, Qcap: int = 512,
                         A_max: int = 8, horizon: int = 10_000,
                         work_steps: int | None = None,
                         window: int | None = None,
                         fault_rate: float = 0.0, repair_rate: float = 1.0,
                         max_requeue: int = DEFAULT_MAX_REQUEUE,
                         strict: bool = False) -> PolicyResult:
    """Simulate multi-resource BF-J/S for one ``Workload`` and key."""
    workload.check_sampler()
    streams = make_streams(key, workload.lam, workload.mu, workload.sampler,
                           L=L, K=K, A_max=A_max, horizon=horizon,
                           num_resources=workload.num_resources,
                           fault_rate=fault_rate, repair_rate=repair_rate)
    return run_bfjs_mr_trace(streams, L=L, K=K, Qcap=Qcap, A_max=A_max,
                             engine=engine, work_steps=work_steps,
                             capacity=workload.capacity, window=window,
                             max_requeue=max_requeue, strict=strict)


def monte_carlo_bfjs_mr_workload(workload, keys, *, engine: str = "scan",
                                 L: int = 8, K: int = 16, Qcap: int = 512,
                                 A_max: int = 8, horizon: int = 10_000,
                                 work_steps: int | None = None,
                                 window: int | None = None,
                                 fault_rate: float = 0.0,
                                 repair_rate: float = 1.0,
                                 max_requeue: int = DEFAULT_MAX_REQUEUE,
                                 strict: bool = False) -> PolicyResult:
    """One simulated cluster per key ("scan" vmaps; "reference" loops the
    host-side oracle and stacks; "pallas" pre-generates every member's
    streams and runs the fused kernel with the ensemble as the grid —
    degrading to "scan" when the precheck rejects the request)."""
    workload.check_sampler()
    if engine == "reference":
        res = [run_bfjs_mr_workload(workload, k, engine=engine, L=L, K=K,
                                    Qcap=Qcap, A_max=A_max, horizon=horizon,
                                    work_steps=work_steps,
                                    fault_rate=fault_rate,
                                    repair_rate=repair_rate,
                                    max_requeue=max_requeue) for k in keys]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *res)
    if engine == "pallas":
        from repro.kernels.bfjs_mr.ops import (bfjs_mr_simulate,
                                               bfjs_mr_vmem_bytes)
        from repro.kernels.common import (ensemble_plane_bytes,
                                          pallas_precheck, resolve_windows)
        R = int(workload.num_resources)
        # keys is the LOCAL batch under a sharded mesh launch, so the
        # footprint check is per device (core.engine.sharding).
        G = int(keys.shape[0])
        if not pallas_precheck(
                "bfjs-mr", nbytes=bfjs_mr_vmem_bytes(
                    L, K, Qcap, A_max, R, resolve_windows(horizon, window)[0]),
                hbm_bytes=ensemble_plane_bytes(
                    G, horizon,
                    stream_lanes=1 + A_max * R + (L * K + A_max),
                    out_lanes=2 + R),
                fault_plane=fault_rate > 0.0, strict=strict):
            engine = "scan"
        else:
            streams = jax.vmap(
                lambda k: make_streams(k, workload.lam, workload.mu,
                                       workload.sampler, L=L, K=K,
                                       A_max=A_max, horizon=horizon,
                                       num_resources=workload.num_resources)
            )(keys)
            return bfjs_mr_simulate(streams, L=L, K=K, Qcap=Qcap,
                                    A_max=A_max, work_steps=work_steps,
                                    capacity=workload.capacity,
                                    window=window)
    fn = functools.partial(run_bfjs_mr_workload, workload, engine=engine,
                           L=L, K=K, Qcap=Qcap, A_max=A_max,
                           horizon=horizon, work_steps=work_steps,
                           fault_rate=fault_rate, repair_rate=repair_rate,
                           max_requeue=max_requeue)
    return jax.vmap(fn)(keys)
