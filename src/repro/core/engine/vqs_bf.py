"""VQS-BF accelerator engines (paper Section VI, Theorem 4: the VQS 2/3
throughput guarantee with BF-like delay).

Re-expresses the event-driven ``core/vqs_bf.py`` scheduler as fixed-shape
JAX programs on the ``SchedStreams`` stack.  VQS-BF keeps VQS's
configuration machinery (max-weight renewal at server-empty epochs,
subscription wake-ups) but replaces head-of-queue FIFO service with
LARGEST-fit-first pops and adds two Best-Fit passes:

  (i)   with k_1 = 1 the server takes the largest fitting VQ_1 job,
        reserving exactly that job's size (no blanket 2/3 reservation);
  (ii)  the other configured type j* is served largest-fit-first from the
        FULL residual, stopping at k_{j*} resident jobs of that type;
  (iii) the remaining capacity is swept BF-S style: keep taking the
        largest fitting job over ALL virtual queues until nothing fits;
  (iv)  an arrival-side BF-J pass offers every still-queued arrival of the
        slot to the tightest feasible server.

The largest-fit-first multiset is per-VQ size-bucketed rings: one
``(2J, Qcap)`` effective-size plane bucketed by VQ type with first-empty-
slot allocation (pops punch holes; pushes fill the lowest hole), plus a
monotone arrival-sequence plane so "pop the largest job <= cap" is a pure
masked lexicographic reduction — maximum effective size, then lowest VQ
index (the ascending strict-improvement scan of
``VirtualQueues.pop_largest_leq_any``), then smallest sequence stamp
(FIFO among equals, exactly ``SortedJobQueue``'s deque order).

Engines:

  * ``engine="reference"`` — nested ``fori/while/cond`` transcription of
    the numpy scheduler, the behavioural oracle (on trace streams it
    reproduces ``simulate_trace(VQSBF(J), ...)`` bit-for-bit);
  * ``engine="scan"``      — branch-free bounded work list.  Each step
    advances past every pending visited server that cannot place (shared
    max-weight renewal, subscription mask writes) and serves the first
    server that can with ONE pop-and-place (largest-fit depends on the
    post-placement residual, so placements cannot be batched the way
    VQS's head-of-queue prefix-fit can) — a slot costs (#placements + 1)
    early-exit iterations;
  * ``engine="pallas"``    — the fused kernel in ``kernels/vqs_bf`` (rings
    and configurations resident in VMEM, Monte-Carlo ensemble as the grid).

Fixed-shape deviations are counted, never silent: ring overflow in
``dropped``, per-server K-slot overflow and lazily-finished slots in
``truncated`` (``truncated == 0`` is the bit-match precondition).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from ..quantize import RES
from .bfjs import DEFAULT_MAX_REQUEUE
from .ops import k_red_jnp, vq_type_of_grid
from .streams import (INF_SLOT, PolicyResult, SchedStreams, make_streams,
                      resolve_work_steps)

CAP = RES
_INF32 = jnp.iinfo(jnp.int32).max


def _decode_config_bf(row: jax.Array, J: int):
    """(k1, jstar, kstar) of a K_RED row — ``VQS._set_config`` plus the
    k_{j*} cap that VQS-BF's step (ii) enforces."""
    nvq = 2 * J
    j_iota = jnp.arange(nvq)
    k1 = row[1] > 0
    js = jnp.min(jnp.where((row > 0) & (j_iota != 1), j_iota, nvq))
    jsx = jnp.minimum(js, nvq - 1)
    ks = jnp.where(js < nvq, row[jsx], 0).astype(jnp.int32)
    return k1, jnp.where(js == nvq, -1, js).astype(jnp.int32), ks


def _mw_config_bf(confs: jax.Array, qcnt: jax.Array, J: int):
    """First-index max-weight row over K_RED (paper Eq. 8, np.argmax ties)."""
    w = confs @ qcnt
    c_iota = jnp.arange(confs.shape[0])
    i = jnp.min(jnp.where(w == w.max(), c_iota, confs.shape[0]))
    row = confs[jnp.minimum(i, confs.shape[0] - 1)]
    return _decode_config_bf(row, J)


def _pop_largest(ring_eff, ring_seq, rows_ok, cap):
    """Locate the pop of ``VirtualQueues.pop_largest_leq_any`` restricted to
    ``rows_ok``: maximum effective size <= cap, ties to the lowest VQ index,
    FIFO among equals via the smallest sequence stamp.  Returns
    ``(found, vq, pos)`` with clamped-in-range indices when not found."""
    nvq, Qcap = ring_eff.shape
    j_iota = jnp.arange(nvq)
    q_iota = jnp.arange(Qcap)
    elig = (ring_eff > 0) & rows_ok[:, None] & (ring_eff <= cap)
    best_eff = jnp.max(jnp.where(elig, ring_eff, 0))
    cand = elig & (ring_eff == best_eff)
    vq = jnp.min(jnp.where(cand.any(axis=1), j_iota, nvq))
    found = vq < nvq
    vqc = jnp.minimum(vq, nvq - 1)
    row_cand = cand[vqc]
    seq_row = ring_seq[vqc]
    best_seq = jnp.min(jnp.where(row_cand, seq_row, _INF32))
    pos = jnp.min(jnp.where(row_cand & (seq_row == best_seq), q_iota, Qcap))
    return found, vqc, jnp.minimum(pos, Qcap - 1)


def _push_arrivals_bf(ring_eff, ring_dur, ring_seq, qcnt, dropped, seq_ctr,
                      n_t, sizes_t, durs_t, *, J, Qcap, A_max,
                      ring_try=None):
    """Classify + bucket one slot's arrivals (vectorized, order-exact).

    Every arrival lands in the lowest empty slot of its VQ's bucket ring
    (lane order within the slot — the rank-into-empty-slots scatter below
    is exactly A_max sequential first-empty pushes) and is stamped with a
    monotone sequence number so largest-fit pops stay FIFO among equals.
    Arrivals whose bucket is full are dropped and counted.  Returns the
    per-lane ``(vq, pos, seq, eff, dur, landed)`` records the slot's
    arrival-side BF-J pass keys on.
    """
    nvq = 2 * J
    a_iota = jnp.arange(A_max)
    j_iota = jnp.arange(nvq)
    q_iota = jnp.arange(Qcap)
    dur_off = durs_t.shape[0] - A_max
    g = jnp.maximum(jnp.round(sizes_t * RES), 1.0).astype(jnp.int32)
    vq = vq_type_of_grid(g, J)
    eff = jnp.where(vq == nvq - 1, jnp.maximum(g, RES >> J), g)
    dur = durs_t[dur_off + a_iota]
    valid = a_iota < n_t
    oh = (vq[:, None] == j_iota[None, :]) & valid[:, None]      # (A, 2J)
    rank = ((jnp.cumsum(oh.astype(jnp.int32), axis=0) - 1) * oh).sum(1)
    emp = ring_eff == 0
    erank = jnp.cumsum(emp.astype(jnp.int32), axis=1) - 1       # (2J, Qcap)
    empty_cnt = emp.sum(axis=1)
    land = valid & (rank < empty_cnt[vq])
    sel = emp[vq] & (erank[vq] == rank[:, None])                # (A, Qcap)
    pos = jnp.minimum(jnp.min(jnp.where(sel, q_iota[None, :], Qcap), axis=1),
                      Qcap - 1)
    seq = seq_ctr + a_iota
    vq_w = jnp.where(land, vq, nvq)
    ring_eff = ring_eff.at[vq_w, pos].set(eff, mode="drop")
    ring_dur = ring_dur.at[vq_w, pos].set(dur, mode="drop")
    ring_seq = ring_seq.at[vq_w, pos].set(seq, mode="drop")
    if ring_try is not None:
        ring_try = ring_try.at[vq_w, pos].set(0, mode="drop")
    qcnt = qcnt + (oh & land[:, None]).sum(0).astype(jnp.int32)
    dropped = dropped + (valid & ~land).sum()
    arrived = oh.any(0)
    lanes = (vq, pos, seq, eff, dur, land)
    return (ring_eff, ring_dur, ring_seq, qcnt, dropped, seq_ctr + A_max,
            arrived, ring_try, lanes)


def _preempt_rings_bf(srv, dep, vqof, ring_eff, ring_dur, ring_seq, ring_try,
                      qcnt, seq_ctr, srv_try, up_t, t, max_requeue,
                      *, J, Qcap):
    """Evict every job resident on a down server (DESIGN.md §9), VQS-BF
    form: victims below the retry bound re-enter their own bucket ring in
    row-major ``(server, k-slot)`` order — first-empty slots, fresh
    sequence stamps (so they queue behind every already-waiting equal-size
    job, the same tail-append rule as the VQS rings) — with their
    REMAINING duration and ``tries + 1``; victims past the bound or whose
    bucket is full are lost.  Shared verbatim by the scan engine and the
    reference oracle."""
    nvq = 2 * J
    L, K = srv.shape
    j_iota = jnp.arange(nvq)
    q_iota = jnp.arange(Qcap)
    victim = (~up_t)[:, None] & (srv > 0)                       # (L, K)
    elig = (victim & (srv_try < max_requeue)).reshape(-1)       # (L*K,)
    vq = jnp.where(elig, vqof.reshape(-1), nvq)
    vqc = jnp.minimum(vq, nvq - 1)
    oh = vq[:, None] == j_iota[None, :]                         # (L*K, 2J)
    rank = ((jnp.cumsum(oh.astype(jnp.int32), axis=0) - 1) * oh).sum(1)
    emp = ring_eff == 0
    erank = jnp.cumsum(emp.astype(jnp.int32), axis=1) - 1
    empty_cnt = emp.sum(axis=1)
    land = elig & (rank < empty_cnt[vqc])
    sel = emp[vqc] & (erank[vqc] == rank[:, None])              # (L*K, Qcap)
    pos = jnp.minimum(jnp.min(jnp.where(sel, q_iota[None, :], Qcap), axis=1),
                      Qcap - 1)
    rem = jnp.maximum(dep.reshape(-1) - t, 1)   # remaining service slots
    vq_w = jnp.where(land, vq, nvq)
    ring_eff = ring_eff.at[vq_w, pos].set(srv.reshape(-1), mode="drop")
    ring_dur = ring_dur.at[vq_w, pos].set(rem, mode="drop")
    ring_seq = ring_seq.at[vq_w, pos].set(seq_ctr + jnp.arange(L * K),
                                          mode="drop")
    ring_try = ring_try.at[vq_w, pos].set(srv_try.reshape(-1) + 1,
                                          mode="drop")
    qcnt = qcnt + (oh & land[:, None]).sum(0).astype(jnp.int32)
    re_arrived = (oh & land[:, None]).any(0)
    n_vict = victim.sum().astype(jnp.int32)
    n_req = land.sum().astype(jnp.int32)
    srv = jnp.where(victim, 0, srv)
    dep = jnp.where(victim, INF_SLOT, dep)
    vqof = jnp.where(victim, -1, vqof)
    srv_try = jnp.where(victim, 0, srv_try)
    return (srv, dep, vqof, ring_eff, ring_dur, ring_seq, ring_try, qcnt,
            seq_ctr + L * K, srv_try, n_vict, n_req, n_vict - n_req,
            re_arrived)


def _arrival_bf_pass(srv, dep, vqof, ring_eff, ring_seq, qcnt, in_empty,
                     srv_try, trunc, t, lanes, up_t, *, L, K, A_max,
                     faulted):
    """The slot's closing BF-J pass (``VQSBF.schedule`` tail): each arrival
    still sitting in its bucket (its sequence stamp survived the serve
    pass) goes to the tightest feasible server — minimum residual >= size,
    ties to the smallest server id, exactly ``Cluster.tightest_feasible``.
    Shared verbatim by the reference oracle and the scan engine (the pass
    is already sequential in the numpy scheduler, so an unrolled A_max
    loop IS the branch-free form)."""
    a_vq, a_pos, a_seq, a_eff, a_dur, a_land = lanes
    nvq = ring_eff.shape[0]
    l_iota = jnp.arange(L)
    k_iota = jnp.arange(K)
    for a in range(A_max):
        vq_a, pos_a = a_vq[a], a_pos[a]
        queued = a_land[a] & (ring_eff[vq_a, pos_a] > 0) \
            & (ring_seq[vq_a, pos_a] == a_seq[a])
        resid = CAP - srv.sum(axis=1)
        cand = resid >= a_eff[a]
        if faulted:
            cand = cand & up_t
        rbest = jnp.min(jnp.where(cand, resid, _INF32))
        s = jnp.min(jnp.where(cand & (resid == rbest), l_iota, L))
        do = queued & (s < L)
        sc = jnp.minimum(s, L - 1)
        kfree = jnp.min(jnp.where(srv[sc] == 0, k_iota, K))
        ok = kfree < K
        kw = jnp.where(do & ok, jnp.minimum(kfree, K - 1), K)
        srv = srv.at[sc, kw].set(a_eff[a], mode="drop")
        dep = dep.at[sc, kw].set(t + a_dur[a], mode="drop")
        vqof = vqof.at[sc, kw].set(vq_a, mode="drop")
        if faulted:  # fresh arrivals carry zero retries
            srv_try = srv_try.at[sc, kw].set(0, mode="drop")
        cvq = jnp.where(do, vq_a, nvq)
        ring_eff = ring_eff.at[cvq, pos_a].set(0, mode="drop")
        qcnt = qcnt.at[cvq].add(-1, mode="drop")
        trunc = trunc + (do & ~ok).astype(jnp.int32)
        in_empty = in_empty & ~((l_iota == s) & do)
    return srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try, trunc


def _init_state(J: int, L: int, K: int, Qcap: int):
    nvq = 2 * J
    zero = jnp.zeros((), jnp.int32)
    return (
        jnp.zeros((L, K), jnp.int32),              # srv (eff sizes)
        jnp.full((L, K), INF_SLOT, jnp.int32),     # dep
        jnp.full((L, K), -1, jnp.int32),           # vqof
        jnp.zeros((nvq, Qcap), jnp.int32),         # ring_eff (0 == empty)
        jnp.ones((nvq, Qcap), jnp.int32),          # ring_dur
        jnp.zeros((nvq, Qcap), jnp.int32),         # ring_seq
        jnp.zeros((nvq,), jnp.int32),              # qcnt
        zero,                                      # seq_ctr
        jnp.zeros((L,), bool),                     # cfg_k1
        jnp.full((L,), -1, jnp.int32),             # cfg_js
        jnp.zeros((L,), jnp.int32),                # cfg_ks
        jnp.zeros((L,), bool),                     # has_cfg
        jnp.ones((L,), bool),                      # in_empty (all start empty)
        jnp.zeros((L, nvq), bool),                 # want
        zero, zero, zero,                          # t, dropped, truncated
        # fault-injection planes (zeros/ones when fault-free):
        jnp.zeros((nvq, Qcap), jnp.int32),         # ring_try
        jnp.zeros((L, K), jnp.int32),              # srv_try
        zero, zero, zero,                          # preempted, requeued, lost
        jnp.ones((L,), bool),                      # up_last
    )


@functools.partial(
    jax.jit, static_argnames=("J", "L", "K", "Qcap", "A_max", "max_requeue"))
def _run_vqs_bf_reference_streams(streams: SchedStreams, J: int, L: int,
                                  K: int, Qcap: int, A_max: int,
                                  max_requeue: int = DEFAULT_MAX_REQUEUE
                                  ) -> PolicyResult:
    """Nested-loop VQS-BF oracle over pre-generated streams.

    A control-flow-faithful transcription of ``core/vqs_bf.py`` +
    ``core/simulator.py``: sorted visit order via ``fori`` over servers,
    per-server renewal ``cond``, the (i) single largest-VQ_1 ``cond``, the
    (ii) capped largest-fit ``while``, the (iii) BF-S ``while`` and the
    closing arrival-side BF-J pass.  Serial and branch-heavy — the
    behavioural anchor the scan engine is tested against (and, through
    trace streams, the bridge to the numpy engine)."""
    nvq = 2 * J
    confs = k_red_jnp(J)
    j_iota = jnp.arange(nvq)
    k_iota = jnp.arange(K)
    faulted = streams.up is not None

    def slot_step(state, inp):
        (srv, dep, vqof, ring_eff, ring_dur, ring_seq, qcnt, seq_ctr,
         cfg_k1, cfg_js, cfg_ks, has_cfg, in_empty, want, t, dropped, trunc,
         ring_try, srv_try, preempted, requeued, lost, up_last) = state
        if faulted:
            n_t, sizes_t, durs_t, up_t = inp
        else:
            n_t, sizes_t, durs_t = inp
            up_t = None

        # 1. departures
        leaving = dep == t
        freed = leaving.any(axis=1)
        n_dep = leaving.sum()
        srv = jnp.where(leaving, 0, srv)
        vqof = jnp.where(leaving, -1, vqof)
        dep = jnp.where(leaving, INF_SLOT, dep)

        # 1b. capacity shocks (shared _preempt_rings_bf rule)
        re_arrived = None
        if faulted:
            srv_try = jnp.where(leaving, 0, srv_try)
            (srv, dep, vqof, ring_eff, ring_dur, ring_seq, ring_try, qcnt,
             seq_ctr, srv_try, n_p, n_r, n_l, re_arrived) = _preempt_rings_bf(
                srv, dep, vqof, ring_eff, ring_dur, ring_seq, ring_try, qcnt,
                seq_ctr, srv_try, up_t, t, max_requeue, J=J, Qcap=Qcap)
            preempted = preempted + n_p
            requeued = requeued + n_r
            lost = lost + n_l
            freed = (freed | (up_t & ~up_last)) & up_t
            up_last = up_t
        empty_now = (srv > 0).sum(axis=1) == 0

        # 2. arrivals
        (ring_eff, ring_dur, ring_seq, qcnt, dropped, seq_ctr, arrived, rt,
         lanes) = _push_arrivals_bf(
            ring_eff, ring_dur, ring_seq, qcnt, dropped, seq_ctr,
            n_t, sizes_t, durs_t, J=J, Qcap=Qcap, A_max=A_max,
            ring_try=ring_try if faulted else None)
        if faulted:
            ring_try = rt
            arrived = arrived | re_arrived

        # 3. visit set
        woken = (want & arrived[None, :]).any(axis=1)
        want = want & ~arrived[None, :]
        visit = freed | woken | (in_empty & (qcnt.sum() > 0))
        if faulted:
            visit = visit & up_t

        # 4. serve visited servers in ascending order
        def visit_server(i, carry):
            def place_from(rows_ok, c):
                (srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try,
                 trunc) = c
                resid = CAP - srv[i].sum()
                _, pvq, ppos = _pop_largest(ring_eff, ring_seq, rows_ok,
                                            resid)
                eff_p = ring_eff[pvq, ppos]
                dur_p = ring_dur[pvq, ppos]
                kfree = jnp.min(jnp.where(srv[i] == 0, k_iota, K))
                ok = kfree < K
                kw = jnp.where(ok, jnp.minimum(kfree, K - 1), K)
                srv = srv.at[i, kw].set(eff_p, mode="drop")
                dep = dep.at[i, kw].set(t + dur_p, mode="drop")
                vqof = vqof.at[i, kw].set(pvq, mode="drop")
                if faulted:  # retry count rides with the job
                    srv_try = srv_try.at[i, kw].set(ring_try[pvq, ppos],
                                                    mode="drop")
                ring_eff = ring_eff.at[pvq, ppos].set(0)
                qcnt = qcnt.at[pvq].add(-1)
                trunc = trunc + (~ok).astype(jnp.int32)
                in_empty = in_empty.at[i].set(False)
                return (srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try,
                        trunc)

            def serve(carry):
                (srv, dep, vqof, ring_eff, qcnt, cfg_k1, cfg_js, cfg_ks,
                 has_cfg, in_empty, want, srv_try, trunc) = carry
                need = empty_now[i] | ~has_cfg[i]
                r_k1, r_js, r_ks = _mw_config_bf(confs, qcnt, J)
                k1 = jnp.where(need, r_k1, cfg_k1[i])
                js = jnp.where(need, r_js, cfg_js[i])
                ks = jnp.where(need, r_ks, cfg_ks[i])
                cfg_k1 = cfg_k1.at[i].set(k1)
                cfg_js = cfg_js.at[i].set(js)
                cfg_ks = cfg_ks.at[i].set(ks)
                has_cfg = has_cfg.at[i].set(True)
                in_empty = in_empty.at[i].set(in_empty[i] | empty_now[i])

                # (i) one largest fitting VQ_1 job, exact reservation
                resid = CAP - srv[i].sum()
                has_vq1 = ((vqof[i] == 1) & (srv[i] > 0)).any()
                fit1 = ((ring_eff[1] > 0) & (ring_eff[1] <= resid)).any()
                do1 = k1 & ~has_vq1 & fit1
                want = want.at[i, 1].set(
                    want[i, 1] | (k1 & ~has_vq1 & (qcnt[1] == 0)))
                c = (srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try,
                     trunc)
                c = jax.lax.cond(do1,
                                 functools.partial(place_from, j_iota == 1),
                                 lambda c: c, c)

                # (ii) largest-fit-first from VQ_{j*}, capped at k_{j*}
                jsx = jnp.maximum(js, 0)
                rows_j = j_iota == jsx

                def jcond(c):
                    srv, _, vqof, ring_eff, *_ = c
                    resid = CAP - srv[i].sum()
                    cnt = ((vqof[i] == jsx) & (srv[i] > 0)).sum()
                    fitj = ((ring_eff > 0) & rows_j[:, None]
                            & (ring_eff <= resid)).any()
                    return (js >= 0) & (cnt < ks) & fitj

                c = jax.lax.while_loop(
                    jcond, functools.partial(place_from, rows_j), c)
                srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try, trunc = c
                cnt_end = ((vqof[i] == jsx) & (srv[i] > 0)).sum()
                subj = (js >= 0) & (cnt_end < ks) & (qcnt[jsx] == 0)
                want = want.at[i, jnp.where(subj, jsx, nvq)].set(
                    True, mode="drop")

                # (iii) BF-S sweep over all VQs
                all_rows = jnp.ones((nvq,), bool)

                def acond(c):
                    srv, _, _, ring_eff, *_ = c
                    resid = CAP - srv[i].sum()
                    return ((ring_eff > 0) & (ring_eff <= resid)).any()

                c = (srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try,
                     trunc)
                c = jax.lax.while_loop(
                    acond, functools.partial(place_from, all_rows), c)
                srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try, trunc = c
                return (srv, dep, vqof, ring_eff, qcnt, cfg_k1, cfg_js,
                        cfg_ks, has_cfg, in_empty, want, srv_try, trunc)

            return jax.lax.cond(visit[i], serve, lambda c: c, carry)

        carry = (srv, dep, vqof, ring_eff, qcnt, cfg_k1, cfg_js, cfg_ks,
                 has_cfg, in_empty, want, srv_try, trunc)
        carry = jax.lax.fori_loop(0, L, visit_server, carry)
        (srv, dep, vqof, ring_eff, qcnt, cfg_k1, cfg_js, cfg_ks,
         has_cfg, in_empty, want, srv_try, trunc) = carry

        # 5. arrival-side BF-J pass over jobs still queued
        (srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try,
         trunc) = _arrival_bf_pass(
            srv, dep, vqof, ring_eff, ring_seq, qcnt, in_empty, srv_try,
            trunc, t, lanes, up_t, L=L, K=K, A_max=A_max, faulted=faulted)

        out = (qcnt.sum().astype(jnp.int32),
               srv.sum().astype(jnp.float32) / RES,
               n_dep.astype(jnp.int32))
        state = (srv, dep, vqof, ring_eff, ring_dur, ring_seq, qcnt,
                 seq_ctr, cfg_k1, cfg_js, cfg_ks, has_cfg, in_empty, want,
                 t + 1, dropped, trunc, ring_try, srv_try, preempted,
                 requeued, lost, up_last)
        return state, out

    state0 = _init_state(J, L, K, Qcap)
    xs = (streams.n, streams.sizes, streams.durs)
    if faulted:
        xs = xs + (streams.up,)
    state, (qlen, occ, ndep) = jax.lax.scan(slot_step, state0, xs)
    return PolicyResult(qlen, occ, jnp.cumsum(ndep), state[15], state[16],
                        state[19], state[20], state[21])


@functools.partial(
    jax.jit,
    static_argnames=("J", "L", "K", "Qcap", "A_max", "work_steps",
                     "max_requeue", "return_state"))
def run_vqs_bf_streams(streams: SchedStreams, J: int, L: int, K: int,
                       Qcap: int, A_max: int, work_steps: int | None = None,
                       max_requeue: int = DEFAULT_MAX_REQUEUE,
                       state: tuple | None = None,
                       return_state: bool = False):
    """Branch-free VQS-BF slot engine over pre-generated streams.

    One ``lax.scan`` over slots; the per-slot serve pass is a work list of
    at most ``work_steps + 1`` masked-select steps (early-exit bounded
    loop).  Each step:

      1. evaluates, for every still-pending visited server, whether it
         could place a job under its effective configuration — step (i)
         when a VQ_1 job fits and none is resident, step (ii) when a
         VQ_{j*} job fits below the k_{j*} cap, step (iii) when ANY queued
         job fits (existence tests are per-bucket minimum queued sizes
         against the server residual);
      2. advances past all pending servers below the first placer,
         applying renewals / ``_empty`` membership / subscriptions as one
         vectorized mask write (order-exact vs the numpy engine);
      3. serves the placer with ONE largest-fit pop-and-place — the pop
         target is re-staged every step from the post-placement state,
         which is exactly the numpy engine's sequential (i) -> (ii) ->
         (iii) order because each stage's predicate is monotone under
         placements (the residual only shrinks and the buckets only drain
         while a server is being served).  The placer stays current until
         nothing fits.

    Unlike VQS's head-of-queue prefix-fit, largest-fit placements cannot
    be batched (each pop depends on the residual the previous pop left),
    so a slot costs one step per placement: size ``work_steps`` to the
    burst you expect (``truncated`` counts the slots finished lazily, and
    the autotuner sweeps the bound per shape).  After the work list, the
    slot closes with the arrival-side BF-J pass shared with the oracle.

    Streams carrying a fault plane run the fault-injected variant
    (``_preempt_rings_bf`` eviction, down servers out of the visit set and
    infeasible for the BF-J pass).  ``state=`` / ``return_state=True``
    thread the complete scan carry for crash-safe chunked sweeps and
    streaming ingestion (DESIGN.md §9/§12).
    """
    nvq = 2 * J
    confs = k_red_jnp(J)
    W = resolve_work_steps(work_steps, A_max)
    l_iota = jnp.arange(L)
    j_iota = jnp.arange(nvq)
    k_iota = jnp.arange(K)
    faulted = streams.up is not None

    def slot_step(state, inp):
        (srv, dep, vqof, ring_eff, ring_dur, ring_seq, qcnt, seq_ctr,
         cfg_k1, cfg_js, cfg_ks, has_cfg, in_empty, want, t, dropped, trunc,
         ring_try, srv_try, preempted, requeued, lost, up_last) = state
        if faulted:
            n_t, sizes_t, durs_t, up_t = inp
        else:
            n_t, sizes_t, durs_t = inp
            up_t = None

        # 1. departures
        leaving = dep == t
        freed = leaving.any(axis=1)
        n_dep = leaving.sum()
        srv = jnp.where(leaving, 0, srv)
        vqof = jnp.where(leaving, -1, vqof)
        dep = jnp.where(leaving, INF_SLOT, dep)

        # 1b. capacity shocks (identical rule to the reference oracle)
        re_arrived = None
        if faulted:
            srv_try = jnp.where(leaving, 0, srv_try)
            (srv, dep, vqof, ring_eff, ring_dur, ring_seq, ring_try, qcnt,
             seq_ctr, srv_try, n_p, n_r, n_l, re_arrived) = _preempt_rings_bf(
                srv, dep, vqof, ring_eff, ring_dur, ring_seq, ring_try, qcnt,
                seq_ctr, srv_try, up_t, t, max_requeue, J=J, Qcap=Qcap)
            preempted = preempted + n_p
            requeued = requeued + n_r
            lost = lost + n_l
            freed = (freed | (up_t & ~up_last)) & up_t
            up_last = up_t
        empty_now = (srv > 0).sum(axis=1) == 0

        # 2. arrivals
        (ring_eff, ring_dur, ring_seq, qcnt, dropped, seq_ctr, arrived, rt,
         lanes) = _push_arrivals_bf(
            ring_eff, ring_dur, ring_seq, qcnt, dropped, seq_ctr,
            n_t, sizes_t, durs_t, J=J, Qcap=Qcap, A_max=A_max,
            ring_try=ring_try if faulted else None)
        if faulted:
            ring_try = rt
            arrived = arrived | re_arrived

        # 3. visit set
        woken = (want & arrived[None, :]).any(axis=1)
        want = want & ~arrived[None, :]
        visit = freed | woken | (in_empty & (qcnt.sum() > 0))
        if faulted:
            visit = visit & up_t
        renew_needed = visit & (empty_now | ~has_cfg)

        # 4. bounded work list (see docstring)
        def work(carry):
            (srv, dep, vqof, ring_eff, qcnt, cfg_k1, cfg_js, cfg_ks,
             has_cfg, in_empty, want, touched, advanced, trunc, n_steps,
             srv_try) = carry
            pending = visit & ~advanced
            occ_ring = ring_eff > 0
            hx = qcnt > 0
            row_min = jnp.min(jnp.where(occ_ring, ring_eff, _INF32),
                              axis=1)                           # (2J,)
            glob_min = jnp.min(row_min)

            # shared renewal candidate + per-server effective configuration
            r_k1, r_js, r_ks = _mw_config_bf(confs, qcnt, J)
            ren = renew_needed & ~touched
            eff_k1 = jnp.where(ren, r_k1, cfg_k1)
            eff_js = jnp.where(ren, r_js, cfg_js)
            eff_ks = jnp.where(ren, r_ks, cfg_ks)

            occ = srv.sum(axis=1)
            resid = CAP - occ
            has_vq1 = ((vqof == 1) & (srv > 0)).any(axis=1)
            js_oh = eff_js[:, None] == j_iota[None, :]          # (L, 2J)
            js_min = jnp.min(jnp.where(js_oh, row_min[None, :], _INF32),
                             axis=1)
            js_ex = (js_oh & hx[None, :]).any(axis=1)
            cnt_js = ((vqof == eff_js[:, None]) & (srv > 0)).sum(axis=1)

            k1_can = eff_k1 & ~has_vq1 & (row_min[1] <= resid)
            js_can = (eff_js >= 0) & (cnt_js < eff_ks) & (js_min <= resid)
            any_can = glob_min <= resid
            would = pending & (k1_can | js_can | any_can)

            placer = jnp.min(jnp.where(would, l_iota, L))
            tch = pending & (l_iota <= placer)
            adv = pending & (l_iota < placer)

            do_ren = tch & ren
            cfg_k1 = jnp.where(do_ren, r_k1, cfg_k1)
            cfg_js = jnp.where(do_ren, r_js, cfg_js)
            cfg_ks = jnp.where(do_ren, r_ks, cfg_ks)
            has_cfg = has_cfg | tch
            # _empty membership is granted at FIRST touch only (numpy adds
            # at visit time, before serving) — see engine/vqs.py.
            in_empty = in_empty | (tch & ~touched & empty_now)
            touched = touched | tch
            advanced = advanced | adv

            # subscriptions of the servers advanced past
            sub1 = adv & eff_k1 & ~has_vq1 & ~hx[1]
            subj = adv & (eff_js >= 0) & (cnt_js < eff_ks) & ~js_ex
            want = want | (sub1[:, None] & (j_iota[None, :] == 1)) \
                        | (subj[:, None] & js_oh)

            # serve the placer: one largest-fit pop-and-place, staged
            # (i) -> (ii) -> (iii)
            any_p = placer < L
            s = jnp.minimum(placer, L - 1)
            do1 = k1_can[s]
            doj = ~do1 & js_can[s]
            rows_ok = jnp.where(
                do1, j_iota == 1,
                jnp.where(doj, j_iota == jnp.maximum(eff_js[s], 0),
                          jnp.ones((nvq,), bool)))
            found, pvq, ppos = _pop_largest(ring_eff, ring_seq, rows_ok,
                                            resid[s])
            do_place = any_p & found
            eff_p = ring_eff[pvq, ppos]
            dur_p = ring_dur[pvq, ppos]
            kfree = jnp.min(jnp.where(srv[s] == 0, k_iota, K))
            ok = kfree < K
            kw = jnp.where(do_place & ok, jnp.minimum(kfree, K - 1), K)
            srv = srv.at[s, kw].set(eff_p, mode="drop")
            dep = dep.at[s, kw].set(t + dur_p, mode="drop")
            vqof = vqof.at[s, kw].set(pvq, mode="drop")
            if faulted:  # retry counts ride with the placed job
                srv_try = srv_try.at[s, kw].set(ring_try[pvq, ppos],
                                                mode="drop")
            cvq = jnp.where(do_place, pvq, nvq)
            ring_eff = ring_eff.at[cvq, ppos].set(0, mode="drop")
            qcnt = qcnt.at[cvq].add(-1, mode="drop")
            trunc = trunc + (do_place & ~ok).astype(jnp.int32)  # K-overflow
            in_empty = in_empty & ~((l_iota == placer) & do_place)
            return (srv, dep, vqof, ring_eff, qcnt, cfg_k1, cfg_js, cfg_ks,
                    has_cfg, in_empty, want, touched, advanced, trunc,
                    n_steps + 1, srv_try)

        def unfinished(carry):
            advanced, n_steps = carry[12], carry[14]
            return (visit & ~advanced).any() & (n_steps <= W)

        carry = (srv, dep, vqof, ring_eff, qcnt, cfg_k1, cfg_js, cfg_ks,
                 has_cfg, in_empty, want, jnp.zeros((L,), bool),
                 jnp.zeros((L,), bool), trunc, jnp.zeros((), jnp.int32),
                 srv_try)
        carry = jax.lax.while_loop(unfinished, work, carry)
        (srv, dep, vqof, ring_eff, qcnt, cfg_k1, cfg_js, cfg_ks,
         has_cfg, in_empty, want, _, advanced, trunc, _, srv_try) = carry
        # cap hit with servers still unserved: the slot finished lazily
        trunc = trunc + (visit & ~advanced).any().astype(jnp.int32)

        # 5. arrival-side BF-J pass over jobs still queued
        (srv, dep, vqof, ring_eff, qcnt, in_empty, srv_try,
         trunc) = _arrival_bf_pass(
            srv, dep, vqof, ring_eff, ring_seq, qcnt, in_empty, srv_try,
            trunc, t, lanes, up_t, L=L, K=K, A_max=A_max, faulted=faulted)

        out = (qcnt.sum().astype(jnp.int32),
               srv.sum().astype(jnp.float32) / RES,
               n_dep.astype(jnp.int32))
        state = (srv, dep, vqof, ring_eff, ring_dur, ring_seq, qcnt,
                 seq_ctr, cfg_k1, cfg_js, cfg_ks, has_cfg, in_empty, want,
                 t + 1, dropped, trunc, ring_try, srv_try, preempted,
                 requeued, lost, up_last)
        return state, out

    if state is None:
        state = _init_state(J, L, K, Qcap)
    xs = (streams.n, streams.sizes, streams.durs)
    if faulted:
        xs = xs + (streams.up,)
    state, (qlen, occ, ndep) = jax.lax.scan(slot_step, state, xs)
    res = PolicyResult(qlen, occ, jnp.cumsum(ndep), state[15], state[16],
                       state[19], state[20], state[21])
    return (res, state) if return_state else res


def run_vqs_bf_trace(streams: SchedStreams, *, J: int, L: int, K: int,
                     Qcap: int, A_max: int, engine: str = "scan",
                     work_steps: int | None = None,
                     window: int | None = None,
                     max_requeue: int = DEFAULT_MAX_REQUEUE,
                     strict: bool = False) -> PolicyResult:
    """Run one VQS-BF simulation over explicit streams (random or trace).
    ``window`` is the Pallas kernel's VMEM time-window length (must divide
    the horizon; ignored by the other engines)."""
    if engine == "reference":
        return _run_vqs_bf_reference_streams(streams, J=J, L=L, K=K,
                                             Qcap=Qcap, A_max=A_max,
                                             max_requeue=max_requeue)
    if engine == "scan":
        return run_vqs_bf_streams(streams, J=J, L=L, K=K, Qcap=Qcap,
                                  A_max=A_max, work_steps=work_steps,
                                  max_requeue=max_requeue)
    if engine == "pallas":
        from repro.kernels.common import (ensemble_plane_bytes,
                                          pallas_precheck, resolve_windows)
        from repro.kernels.vqs_bf.ops import (vqs_bf_simulate,
                                              vqs_bf_vmem_bytes)
        T, D = streams.n.shape[0], streams.durs.shape[-1]
        if not pallas_precheck(
                "vqs_bf", nbytes=vqs_bf_vmem_bytes(
                    J, L, K, Qcap, A_max, resolve_windows(T, window)[0]),
                hbm_bytes=ensemble_plane_bytes(
                    1, T, stream_lanes=1 + A_max + D, out_lanes=3),
                fault_plane=streams.up is not None, strict=strict):
            return run_vqs_bf_streams(streams, J=J, L=L, K=K, Qcap=Qcap,
                                      A_max=A_max, work_steps=work_steps,
                                      max_requeue=max_requeue)
        batched = jax.tree.map(lambda x: x[None], streams)
        res = vqs_bf_simulate(batched, J=J, L=L, K=K, Qcap=Qcap,
                              A_max=A_max, work_steps=work_steps,
                              window=window)
        return jax.tree.map(lambda x: x[0], res)
    raise ValueError(f"unknown engine {engine!r}")


def run_vqs_bf(key: jax.Array, lam: float, mu: float,
               sampler: Callable[[jax.Array, int], jax.Array],
               J: int = 4, L: int = 8, K: int = 16, Qcap: int = 512,
               A_max: int = 8, horizon: int = 10_000, engine: str = "scan",
               work_steps: int | None = None,
               window: int | None = None,
               fault_rate: float = 0.0, repair_rate: float = 1.0,
               max_requeue: int = DEFAULT_MAX_REQUEUE,
               strict: bool = False) -> PolicyResult:
    """Simulate VQS-BF on L unit-capacity servers for ``horizon`` slots.

    Randomness is hoisted into ``make_streams`` exactly as for the other
    policies, so the streams (and any fault plane) are bitwise identical
    to a VQS run on the same key — the delay comparison in the paper's
    Section VI figures is a same-streams comparison here too.
    """
    streams = make_streams(key, lam, mu, sampler, L=L, K=K, A_max=A_max,
                           horizon=horizon, fault_rate=fault_rate,
                           repair_rate=repair_rate)
    return run_vqs_bf_trace(streams, J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
                            engine=engine, work_steps=work_steps,
                            window=window, max_requeue=max_requeue,
                            strict=strict)


def run_vqs_bf_workload(workload, key: jax.Array, *, engine: str = "scan",
                        **config) -> PolicyResult:
    """Workload-first adapter: the registry entry behind
    ``run_policy(workload, policy="vqs-bf", ...)``.  VQS-BF partitions
    scalar sizes; vector workloads are rejected loudly."""
    workload.require_scalar("vqs-bf")
    workload.check_sampler()
    return run_vqs_bf(key, workload.lam, workload.mu, workload.sampler,
                      engine=engine, **config)


def monte_carlo_vqs_bf_workload(workload, keys: jax.Array, *,
                                engine: str = "scan",
                                **config) -> PolicyResult:
    """Workload-first adapter for ``monte_carlo_policy(policy="vqs-bf")``."""
    workload.require_scalar("vqs-bf")
    workload.check_sampler()
    return monte_carlo_vqs_bf(keys, workload.lam, workload.mu,
                              workload.sampler, engine=engine, **config)


def monte_carlo_vqs_bf(keys: jax.Array, lam: float, mu: float, sampler,
                       engine: str = "scan", work_steps: int | None = None,
                       window: int | None = None, J: int = 4, L: int = 8,
                       K: int = 16, Qcap: int = 512, A_max: int = 8,
                       horizon: int = 10_000, fault_rate: float = 0.0,
                       repair_rate: float = 1.0,
                       max_requeue: int = DEFAULT_MAX_REQUEUE,
                       strict: bool = False) -> PolicyResult:
    """One simulated cluster per key (vmap; "pallas" uses the kernel grid)."""
    if engine == "pallas":
        from repro.kernels.common import (ensemble_plane_bytes,
                                          pallas_precheck, resolve_windows)
        from repro.kernels.vqs_bf.ops import (vqs_bf_simulate,
                                              vqs_bf_vmem_bytes)
        # keys is the LOCAL batch under a sharded mesh launch, so the
        # footprint check is per device (core.engine.sharding).
        G = int(keys.shape[0])
        if not pallas_precheck(
                "vqs_bf", nbytes=vqs_bf_vmem_bytes(
                    J, L, K, Qcap, A_max, resolve_windows(horizon, window)[0]),
                hbm_bytes=ensemble_plane_bytes(
                    G, horizon, stream_lanes=1 + A_max + (L * K + A_max),
                    out_lanes=3),
                fault_plane=fault_rate > 0.0, strict=strict):
            engine = "scan"
        else:
            streams = jax.vmap(
                lambda k: make_streams(k, lam, mu, sampler, L=L, K=K,
                                       A_max=A_max, horizon=horizon))(keys)
            return vqs_bf_simulate(streams, J=J, L=L, K=K, Qcap=Qcap,
                                   A_max=A_max, work_steps=work_steps,
                                   window=window)
    fn = functools.partial(run_vqs_bf, lam=lam, mu=mu, sampler=sampler,
                           engine=engine, work_steps=work_steps,
                           J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
                           horizon=horizon, fault_rate=fault_rate,
                           repair_rate=repair_rate, max_requeue=max_requeue)
    return jax.vmap(fn)(keys)
