"""VQS accelerator engines (paper Section V, Theorem 3: >= 2/3 rho*).

Re-expresses the event-driven ``core/vqs.py`` scheduler as fixed-shape JAX
programs that share the ``SchedStreams`` stack with the BF-J/S engines:

  * ``engine="reference"`` — a nested ``fori/while/cond`` transcription of
    the numpy scheduler (visit sets, configuration renewal at server-empty
    epochs, head-of-VQ packing, subscription wake-ups), kept as the
    behavioural oracle: on trace-driven streams it reproduces
    ``simulate_trace(VQS(J), ...)`` queue trajectories bit-for-bit;
  * ``engine="scan"``      — the branch-free rewrite: per slot, a bounded
    work list of masked-select steps.  Each step (a) advances past EVERY
    pending visited server that cannot place (their renewals collapse to
    one shared max-weight configuration because the VQ-size vector is
    unchanged between placements, and their subscriptions are pure mask
    writes), then (b) fully serves the first server that can place — the
    head-of-VQ packing loop becomes a prefix-fit over a ``drain``-wide
    window of consecutive ring entries, so one step can pack a whole
    server.  Steps therefore scale with *placing* visits, not visits;
  * ``engine="pallas"``    — the fused kernel in ``kernels/vqs`` (rings,
    configurations and subscriptions resident in VMEM; the Monte-Carlo
    ensemble is the kernel grid).

All capacity arithmetic is exact integer math on the ``quantize.RES`` grid
(the same grid the event-driven engine uses), so "bit-match" is equality of
integer trajectories — no float tolerance anywhere.

Fixed-shape deviations (counted, never silent):

  * each virtual queue is a ``Qcap``-entry ring; arrivals that overflow
    their ring are dropped and counted (``dropped``);
  * each server holds at most ``K`` jobs; a placement the paper's unbounded
    model would make onto a full server is counted in ``truncated``
    (choose ``K >= 2**J`` to make this impossible);
  * a slot that needs more than ``work_steps`` placing servers is finished
    lazily (remaining placements postponed to later wake-ups) and counted
    in ``truncated``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from ..quantize import RES, TWO_THIRDS
from .bfjs import DEFAULT_MAX_REQUEUE
from .ops import k_red_jnp, vq_type_of_grid
from .streams import (INF_SLOT, PolicyResult, SchedStreams, make_streams,
                      resolve_work_steps)

CAP = RES             # unit server capacity on the grid
RESERVE = TWO_THIRDS  # (2*CAP + 1) // 3, the paper's VQ_1 reservation


def _default_drain(K: int, J: int) -> int:
    # widest useful packing burst: a server cannot hold more than K jobs,
    # nor more than 2**J of the smallest effective size CAP >> J.
    return max(1, min(K, 1 << J, 16))


def _decode_config(row: jax.Array, J: int) -> tuple[jax.Array, jax.Array]:
    """(k1, jstar) of a K_RED row — jstar is the first nonzero type != 1
    (-1 if none), replicating ``VQS._set_config``."""
    j_iota = jnp.arange(2 * J)
    k1 = row[1] > 0
    js = jnp.min(jnp.where((row > 0) & (j_iota != 1), j_iota, 2 * J))
    return k1, jnp.where(js == 2 * J, -1, js).astype(jnp.int32)


def _mw_config(confs: jax.Array, qcnt: jax.Array, J: int):
    """First-index max-weight row over K_RED (paper Eq. 8, np.argmax ties)."""
    w = confs @ qcnt
    c_iota = jnp.arange(confs.shape[0])
    i = jnp.min(jnp.where(w == w.max(), c_iota, confs.shape[0]))
    row = confs[jnp.minimum(i, confs.shape[0] - 1)]
    return _decode_config(row, J)


def _push_arrivals(ring_eff, ring_dur, head, qcnt, dropped,
                   n_t, sizes_t, durs_t, *, J, Qcap, A_max, ring_try=None):
    """Classify + enqueue one slot's arrivals (vectorized, order-exact).

    Durations come from the LAST ``A_max`` lanes of the duration stream —
    the per-arrival lanes shared by make_streams (full-width) and
    streams_from_trace (lanes only), so a job's duration always travels
    with the job.  Returns updated rings/counts plus the ``arrived`` type
    mask that drives subscription wake-ups (all sampled arrivals wake, as
    in the numpy engine — a dropped arrival already flags the run via
    ``dropped``).

    On fault-injected runs the rings additionally carry a retry-count plane
    (``ring_try``, written by ``_preempt_rings``); fresh arrivals zero their
    entry so a ring slot's count always belongs to the job stored there.
    """
    nvq = 2 * J
    a_iota = jnp.arange(A_max)
    j_iota = jnp.arange(nvq)
    dur_off = durs_t.shape[0] - A_max
    g = jnp.maximum(jnp.round(sizes_t * RES), 1.0).astype(jnp.int32)
    vq = vq_type_of_grid(g, J)
    eff = jnp.where(vq == nvq - 1, jnp.maximum(g, RES >> J), g)
    valid = a_iota < n_t
    oh = (vq[:, None] == j_iota[None, :]) & valid[:, None]      # (A, 2J)
    rank = ((jnp.cumsum(oh.astype(jnp.int32), axis=0) - 1) * oh).sum(1)
    cnt_own = (oh * qcnt[None, :]).sum(1)
    head_own = (oh * head[None, :]).sum(1)
    land = valid & (cnt_own + rank < Qcap)
    pos = (head_own + cnt_own + rank) % Qcap
    vq_w = jnp.where(land, vq, nvq)
    ring_eff = ring_eff.at[vq_w, pos].set(eff, mode="drop")
    ring_dur = ring_dur.at[vq_w, pos].set(durs_t[dur_off + a_iota],
                                          mode="drop")
    if ring_try is not None:
        ring_try = ring_try.at[vq_w, pos].set(0, mode="drop")
    qcnt = qcnt + (oh & land[:, None]).sum(0).astype(jnp.int32)
    dropped = dropped + (valid & ~land).sum()
    arrived = oh.any(0)
    return ring_eff, ring_dur, head, qcnt, dropped, arrived, ring_try


def _preempt_rings(srv, dep, vqof, ring_eff, ring_dur, ring_try, head, qcnt,
                   srv_try, up_t, t, max_requeue, *, J, Qcap):
    """Evict every job resident on a down server (DESIGN.md §9), VQS form.

    Shared verbatim by the scan engine and the reference oracle.  Victims
    below the retry bound re-enter the TAIL of their own virtual queue in
    row-major ``(server, k-slot)`` order — the same one-hot tail-append rule
    as ``_push_arrivals`` — with their REMAINING duration ``dep - t`` and
    ``tries + 1``; victims past the bound (or whose ring is full) are lost.
    Returns the updated planes, the slot's ``(n_preempted, n_requeued,
    n_lost)`` counts, and the ``re_arrived`` type mask of rings that
    received a requeue (it wakes subscribers exactly like an arrival).
    """
    nvq = 2 * J
    j_iota = jnp.arange(nvq)
    victim = (~up_t)[:, None] & (srv > 0)                       # (L, K)
    elig = (victim & (srv_try < max_requeue)).reshape(-1)       # (L*K,)
    vq = jnp.where(elig, vqof.reshape(-1), nvq)
    oh = vq[:, None] == j_iota[None, :]                         # (L*K, 2J)
    rank = ((jnp.cumsum(oh.astype(jnp.int32), axis=0) - 1) * oh).sum(1)
    cnt_own = (oh * qcnt[None, :]).sum(1)
    head_own = (oh * head[None, :]).sum(1)
    land = elig & (cnt_own + rank < Qcap)
    pos = (head_own + cnt_own + rank) % Qcap
    vq_w = jnp.where(land, vq, nvq)
    rem = jnp.maximum(dep.reshape(-1) - t, 1)   # remaining service slots
    ring_eff = ring_eff.at[vq_w, pos].set(srv.reshape(-1), mode="drop")
    ring_dur = ring_dur.at[vq_w, pos].set(rem, mode="drop")
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
    return (srv, dep, vqof, ring_eff, ring_dur, ring_try, head, qcnt,
            srv_try, n_vict, n_req, n_vict - n_req, re_arrived)


@functools.partial(
    jax.jit, static_argnames=("J", "L", "K", "Qcap", "A_max", "max_requeue"))
def _run_vqs_reference_streams(streams: SchedStreams, J: int, L: int, K: int,
                               Qcap: int, A_max: int,
                               max_requeue: int = DEFAULT_MAX_REQUEUE
                               ) -> PolicyResult:
    """Nested-loop VQS oracle over pre-generated streams.

    A control-flow-faithful transcription of ``core/vqs.py`` +
    ``core/simulator.py``: sorted visit order via ``fori`` over servers,
    per-server renewal ``cond``, single-job VQ_1 step, head-of-VQ ``while``
    packing, subscription sets as a boolean (L, 2J) matrix.  Serial and
    branch-heavy — the behavioural anchor the scan engine is tested
    against (and, through trace streams, the bridge to the numpy engine).

    Streams carrying a fault plane run the fault-injected variant through
    the shared ``_preempt_rings`` rule, bit-matched with the scan engine.
    """
    horizon = streams.n.shape[0]
    nvq = 2 * J
    confs = k_red_jnp(J)
    k_iota = jnp.arange(K)
    faulted = streams.up is not None

    def slot_step(state, inp):
        (srv, dep, vqof, ring_eff, ring_dur, head, qcnt,
         cfg_k1, cfg_js, has_cfg, in_empty, want, t, dropped, trunc,
         ring_try, srv_try, preempted, requeued, lost, up_last) = state
        if faulted:
            n_t, sizes_t, durs_t, up_t = inp
        else:
            n_t, sizes_t, durs_t = inp

        # 1. departures
        leaving = dep == t
        freed = leaving.any(axis=1)
        n_dep = leaving.sum()
        srv = jnp.where(leaving, 0, srv)
        vqof = jnp.where(leaving, -1, vqof)
        dep = jnp.where(leaving, INF_SLOT, dep)

        # 1b. capacity shocks: evict down servers into the VQ tails
        # (shared _preempt_rings rule), recoveries count as freed, down
        # servers leave the visit set.
        re_arrived = None
        if faulted:
            srv_try = jnp.where(leaving, 0, srv_try)
            (srv, dep, vqof, ring_eff, ring_dur, ring_try, head, qcnt,
             srv_try, n_p, n_r, n_l, re_arrived) = _preempt_rings(
                srv, dep, vqof, ring_eff, ring_dur, ring_try, head, qcnt,
                srv_try, up_t, t, max_requeue, J=J, Qcap=Qcap)
            preempted = preempted + n_p
            requeued = requeued + n_r
            lost = lost + n_l
            freed = (freed | (up_t & ~up_last)) & up_t
            up_last = up_t
        empty_now = (srv > 0).sum(axis=1) == 0

        # 2. arrivals
        (ring_eff, ring_dur, head, qcnt, dropped, arrived,
         rt) = _push_arrivals(
            ring_eff, ring_dur, head, qcnt, dropped, n_t, sizes_t, durs_t,
            J=J, Qcap=Qcap, A_max=A_max,
            ring_try=ring_try if faulted else None)
        if faulted:
            ring_try = rt
            arrived = arrived | re_arrived

        # 3. visit set (freed + woken subscribers + empty-with-work)
        woken = (want & arrived[None, :]).any(axis=1)
        want = want & ~arrived[None, :]
        visit = freed | woken | (in_empty & (qcnt.sum() > 0))
        if faulted:
            visit = visit & up_t

        def place_one(i, j, carry):
            srv, dep, vqof, head, qcnt, in_empty, srv_try, trunc = carry
            pos = head[j] % Qcap
            eff_p = ring_eff[j, pos]
            dur_p = ring_dur[j, pos]
            head = head.at[j].add(1)
            qcnt = qcnt.at[j].add(-1)
            row = srv[i]
            slot = jnp.min(jnp.where(row == 0, k_iota, K))
            ok = slot < K
            kw = jnp.minimum(slot, K - 1)
            kw = jnp.where(ok, kw, K)
            srv = srv.at[i, kw].set(eff_p, mode="drop")
            dep = dep.at[i, kw].set(t + dur_p, mode="drop")
            vqof = vqof.at[i, kw].set(j, mode="drop")
            if faulted:  # retry count rides with the job
                srv_try = srv_try.at[i, kw].set(ring_try[j, pos],
                                                mode="drop")
            trunc = trunc + (~ok).astype(jnp.int32)
            in_empty = in_empty.at[i].set(False)
            return srv, dep, vqof, head, qcnt, in_empty, srv_try, trunc

        # 4. serve visited servers in ascending order
        def visit_server(i, carry):
            def serve(carry):
                (srv, dep, vqof, head, qcnt,
                 cfg_k1, cfg_js, has_cfg, in_empty, want, srv_try,
                 trunc) = carry
                need = empty_now[i] | ~has_cfg[i]
                r_k1, r_js = _mw_config(confs, qcnt, J)
                k1 = jnp.where(need, r_k1, cfg_k1[i])
                js = jnp.where(need, r_js, cfg_js[i])
                cfg_k1 = cfg_k1.at[i].set(k1)
                cfg_js = cfg_js.at[i].set(js)
                has_cfg = has_cfg.at[i].set(True)
                in_empty = in_empty.at[i].set(in_empty[i] | empty_now[i])

                # (i) one VQ_1 job into the 2/3 reservation when missing
                resid = CAP - srv[i].sum()
                has_vq1 = ((vqof[i] == 1) & (srv[i] > 0)).any()
                ex1 = qcnt[1] > 0
                he1 = ring_eff[1, head[1] % Qcap]
                do1 = k1 & ~has_vq1 & ex1 & (he1 <= resid)
                want = want.at[i, 1].set(want[i, 1] | (k1 & ~has_vq1 & ~ex1))
                pl = (srv, dep, vqof, head, qcnt, in_empty, srv_try, trunc)
                pl = jax.lax.cond(do1, lambda c: place_one(i, 1, c),
                                  lambda c: c, pl)
                srv, dep, vqof, head, qcnt, in_empty, srv_try, trunc = pl

                # (ii) head-of-VQ_{j*} packing into the unreserved capacity
                other_cap = jnp.where(k1, CAP - RESERVE, CAP)
                jsx = jnp.maximum(js, 0)

                def jcond(c):
                    srv, dep, vqof, head, qcnt, in_empty, srv_try, trunc = c
                    ex = qcnt[jsx] > 0
                    he = ring_eff[jsx, head[jsx] % Qcap]
                    vq1_occ = (srv[i] * (vqof[i] == 1)).sum()
                    other_occ = srv[i].sum() - vq1_occ
                    return (js >= 0) & ex & (other_occ + he <= other_cap)

                pl = (srv, dep, vqof, head, qcnt, in_empty, srv_try, trunc)
                pl = jax.lax.while_loop(jcond,
                                        lambda c: place_one(i, jsx, c), pl)
                srv, dep, vqof, head, qcnt, in_empty, srv_try, trunc = pl
                sub_j = (js >= 0) & (qcnt[jsx] == 0)
                want = want.at[i, jnp.where(sub_j, jsx, nvq)].set(
                    True, mode="drop")
                return (srv, dep, vqof, head, qcnt,
                        cfg_k1, cfg_js, has_cfg, in_empty, want, srv_try,
                        trunc)

            return jax.lax.cond(visit[i], serve, lambda c: c, carry)

        carry = (srv, dep, vqof, head, qcnt,
                 cfg_k1, cfg_js, has_cfg, in_empty, want, srv_try, trunc)
        carry = jax.lax.fori_loop(0, L, visit_server, carry)
        (srv, dep, vqof, head, qcnt,
         cfg_k1, cfg_js, has_cfg, in_empty, want, srv_try, trunc) = carry

        out = (qcnt.sum().astype(jnp.int32),
               srv.sum().astype(jnp.float32) / RES,
               n_dep.astype(jnp.int32))
        state = (srv, dep, vqof, ring_eff, ring_dur, head, qcnt,
                 cfg_k1, cfg_js, has_cfg, in_empty, want, t + 1,
                 dropped, trunc, ring_try, srv_try, preempted, requeued,
                 lost, up_last)
        return state, out

    state0 = _init_state(J, L, K, Qcap)
    xs = (streams.n, streams.sizes, streams.durs)
    if faulted:
        xs = xs + (streams.up,)
    state, (qlen, occ, ndep) = jax.lax.scan(slot_step, state0, xs)
    return PolicyResult(qlen, occ, jnp.cumsum(ndep), state[13], state[14],
                        state[17], state[18], state[19])


def _init_state(J: int, L: int, K: int, Qcap: int):
    nvq = 2 * J
    zero = jnp.zeros((), jnp.int32)
    return (
        jnp.zeros((L, K), jnp.int32),              # srv (eff sizes)
        jnp.full((L, K), INF_SLOT, jnp.int32),     # dep
        jnp.full((L, K), -1, jnp.int32),           # vqof
        jnp.zeros((nvq, Qcap), jnp.int32),         # ring_eff
        jnp.ones((nvq, Qcap), jnp.int32),          # ring_dur
        jnp.zeros((nvq,), jnp.int32),              # head
        jnp.zeros((nvq,), jnp.int32),              # qcnt
        jnp.zeros((L,), bool),                     # cfg_k1
        jnp.full((L,), -1, jnp.int32),             # cfg_js
        jnp.zeros((L,), bool),                     # has_cfg
        jnp.ones((L,), bool),                      # in_empty (all start empty)
        jnp.zeros((L, nvq), bool),                 # want
        zero, zero, zero,                          # t, dropped, truncated
        # fault-injection planes (indices 15+; zeros/ones when fault-free):
        jnp.zeros((nvq, Qcap), jnp.int32),         # ring_try
        jnp.zeros((L, K), jnp.int32),              # srv_try
        zero, zero, zero,                          # preempted, requeued, lost
        jnp.ones((L,), bool),                      # up_last
    )


@functools.partial(
    jax.jit,
    static_argnames=("J", "L", "K", "Qcap", "A_max", "work_steps", "drain",
                     "max_requeue", "return_state"))
def run_vqs_streams(streams: SchedStreams, J: int, L: int, K: int,
                    Qcap: int, A_max: int, work_steps: int | None = None,
                    drain: int | None = None,
                    max_requeue: int = DEFAULT_MAX_REQUEUE,
                    state: tuple | None = None,
                    return_state: bool = False):
    """Branch-free VQS slot engine over pre-generated streams.

    One ``lax.scan`` over slots; the per-slot serve pass is a work list of
    at most ``work_steps + 1`` masked-select steps (an early-exit bounded
    loop: a slot pays for the placements it performs, not for the bound).
    Each step:

      1. evaluates, for every still-pending visited server, whether it
         could place a job under its effective configuration (its own, or —
         for first-touch renewals — the shared max-weight configuration of
         the CURRENT VQ-size vector, identical for every server touched in
         the same step because only placements change the vector);
      2. advances past all pending servers below the first placer,
         applying their renewals / ``_empty`` membership / subscription
         writes as one vectorized mask update (order-exact: they are
         exactly the servers the numpy engine would have served, with the
         same queue state, before the placer);
      3. serves the placer: either the single reserved VQ_1 placement, or
         a prefix-fit batch of up to ``drain`` consecutive head-of-VQ_{j*}
         jobs (the ``while`` packing loop collapsed into one cumsum);
         the placer stays current until it can no longer place.

    When no pending server can place, the same step degenerates to a pure
    advance pass (placement masks all no-ops) that drains the visit list
    and ends the slot.  A slot that exhausts the step bound with servers
    still unserved increments ``truncated`` (finished lazily — never
    silently wrong).

    Streams carrying a fault plane run the fault-injected variant (shared
    ``_preempt_rings`` eviction, down servers out of the visit set) and
    stay bit-matched with the reference oracle.  ``state=`` /
    ``return_state=True`` thread the complete scan carry for crash-safe
    chunked sweeps (DESIGN.md §9).
    """
    horizon = streams.n.shape[0]
    nvq = 2 * J
    confs = k_red_jnp(J)
    W = resolve_work_steps(work_steps, A_max)
    P = drain if drain is not None else _default_drain(K, J)
    l_iota = jnp.arange(L)
    j_iota = jnp.arange(nvq)
    k_iota = jnp.arange(K)
    p_iota = jnp.arange(P)
    faulted = streams.up is not None

    def slot_step(state, inp):
        (srv, dep, vqof, ring_eff, ring_dur, head, qcnt,
         cfg_k1, cfg_js, has_cfg, in_empty, want, t, dropped, trunc,
         ring_try, srv_try, preempted, requeued, lost, up_last) = state
        if faulted:
            n_t, sizes_t, durs_t, up_t = inp
        else:
            n_t, sizes_t, durs_t = inp

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
            (srv, dep, vqof, ring_eff, ring_dur, ring_try, head, qcnt,
             srv_try, n_p, n_r, n_l, re_arrived) = _preempt_rings(
                srv, dep, vqof, ring_eff, ring_dur, ring_try, head, qcnt,
                srv_try, up_t, t, max_requeue, J=J, Qcap=Qcap)
            preempted = preempted + n_p
            requeued = requeued + n_r
            lost = lost + n_l
            freed = (freed | (up_t & ~up_last)) & up_t
            up_last = up_t
        empty_now = (srv > 0).sum(axis=1) == 0

        # 2. arrivals
        (ring_eff, ring_dur, head, qcnt, dropped, arrived,
         rt) = _push_arrivals(
            ring_eff, ring_dur, head, qcnt, dropped, n_t, sizes_t, durs_t,
            J=J, Qcap=Qcap, A_max=A_max,
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

        # 4. bounded work list (see module docstring)
        def work(carry):
            (srv, dep, vqof, head, qcnt, cfg_k1, cfg_js, has_cfg,
             in_empty, want, touched, advanced, trunc, n_steps,
             srv_try) = carry
            pending = visit & ~advanced
            hx = qcnt > 0
            head_effs = jnp.take_along_axis(
                ring_eff, (head % Qcap)[:, None], axis=1)[:, 0]

            # shared renewal candidate + per-server effective configuration
            r_k1, r_js = _mw_config(confs, qcnt, J)
            ren = renew_needed & ~touched
            eff_k1 = jnp.where(ren, r_k1, cfg_k1)
            eff_js = jnp.where(ren, r_js, cfg_js)

            occ = srv.sum(axis=1)
            is1 = (vqof == 1) & (srv > 0)
            vq1_occ = (srv * is1).sum(axis=1)
            has_vq1 = is1.any(axis=1)
            resid = CAP - occ
            other_occ = occ - vq1_occ
            other_cap = jnp.where(eff_k1, CAP - RESERVE, CAP)
            k1_can = eff_k1 & ~has_vq1 & hx[1] & (head_effs[1] <= resid)
            js_oh = eff_js[:, None] == j_iota[None, :]        # (L, 2J)
            js_head = (js_oh * head_effs[None, :]).sum(axis=1)
            js_ex = (js_oh & hx[None, :]).any(axis=1)
            js_can = (eff_js >= 0) & js_ex & (other_occ + js_head <= other_cap)
            would = pending & (k1_can | js_can)

            placer = jnp.min(jnp.where(would, l_iota, L))
            tch = pending & (l_iota <= placer)
            adv = pending & (l_iota < placer)

            do_ren = tch & ren
            cfg_k1 = jnp.where(do_ren, r_k1, cfg_k1)
            cfg_js = jnp.where(do_ren, r_js, cfg_js)
            has_cfg = has_cfg | tch
            # _empty membership is granted at FIRST touch only (numpy adds
            # at visit time, before serving): a placer that emptied at slot
            # start but placed jobs in earlier steps must not be re-marked
            # from the stale empty_now mask when it is advanced past.
            in_empty = in_empty | (tch & ~touched & empty_now)
            touched = touched | tch
            advanced = advanced | adv

            # subscriptions of the servers advanced past (they place
            # nothing, so these are their only state writes)
            sub1 = adv & eff_k1 & ~has_vq1 & ~hx[1]
            subj = adv & (eff_js >= 0) & ~js_ex
            want = want | (sub1[:, None] & (j_iota[None, :] == 1)) \
                        | (subj[:, None] & js_oh)

            # serve the placer
            any_p = placer < L
            s = jnp.minimum(placer, L - 1)
            do_k1 = any_p & k1_can[s]
            j_sel = jnp.where(do_k1, 1, jnp.maximum(eff_js[s], 0))
            wpos = (head[j_sel] + p_iota) % Qcap
            effs_w = ring_eff[j_sel, wpos]
            durs_w = ring_dur[j_sel, wpos]
            in_q = p_iota < qcnt[j_sel]
            fit = in_q & (jnp.cumsum(effs_w) <= other_cap[s] - other_occ[s])
            m = jnp.where(do_k1, 1, fit.sum())
            m = jnp.where(any_p, m, 0)

            row = srv[s]
            es = row == 0
            free_cnt = es.sum()
            slotrank = jnp.cumsum(es.astype(jnp.int32)) - 1
            sel = (es[:, None] & (slotrank[:, None] == p_iota[None, :])
                   & (p_iota[None, :] < m))                   # (K, P)
            placed_k = sel.any(axis=1)
            new_row = row + sel.astype(jnp.int32) @ effs_w
            new_dep = jnp.where(placed_k, t + sel.astype(jnp.int32) @ durs_w,
                                dep[s])
            new_vq = jnp.where(placed_k, j_sel, vqof[s])
            lmask = (l_iota == placer)[:, None]
            srv = jnp.where(lmask, new_row[None, :], srv)
            dep = jnp.where(lmask, new_dep[None, :], dep)
            vqof = jnp.where(lmask, new_vq[None, :], vqof)
            if faulted:  # retry counts ride with the placed jobs
                tries_w = ring_try[j_sel, wpos]
                new_try = jnp.where(placed_k,
                                    sel.astype(jnp.int32) @ tries_w,
                                    srv_try[s])
                srv_try = jnp.where(lmask, new_try[None, :], srv_try)
            jw = jnp.where(any_p, j_sel, nvq)
            head = head.at[jw].add(m, mode="drop")
            qcnt = qcnt.at[jw].add(-m, mode="drop")
            in_empty = in_empty & ~((l_iota == placer) & (m > 0))
            trunc = trunc + jnp.maximum(m - free_cnt, 0)  # K-overflow
            return (srv, dep, vqof, head, qcnt, cfg_k1, cfg_js,
                    has_cfg, in_empty, want, touched, advanced, trunc,
                    n_steps + 1, srv_try)

        # Early-exit bounded loop: when no pending server can place, the
        # body degenerates to the advance-everyone finalization (placement
        # masks are all no-ops), pending empties and the loop exits — so a
        # slot costs (#placing servers + 1) iterations, not the W bound.
        # Each iteration is the same branch-free masked-select program the
        # Pallas kernel unrolls with a fixed trip count.
        def unfinished(carry):
            advanced, n_steps = carry[11], carry[13]
            return (visit & ~advanced).any() & (n_steps <= W)

        carry = (srv, dep, vqof, head, qcnt, cfg_k1, cfg_js, has_cfg,
                 in_empty, want, jnp.zeros((L,), bool), jnp.zeros((L,), bool),
                 trunc, jnp.zeros((), jnp.int32), srv_try)
        carry = jax.lax.while_loop(unfinished, work, carry)
        (srv, dep, vqof, head, qcnt, cfg_k1, cfg_js, has_cfg,
         in_empty, want, _, advanced, trunc, _, srv_try) = carry
        # cap hit with servers still unserved: the slot finished lazily
        trunc = trunc + (visit & ~advanced).any().astype(jnp.int32)

        out = (qcnt.sum().astype(jnp.int32),
               srv.sum().astype(jnp.float32) / RES,
               n_dep.astype(jnp.int32))
        state = (srv, dep, vqof, ring_eff, ring_dur, head, qcnt,
                 cfg_k1, cfg_js, has_cfg, in_empty, want, t + 1,
                 dropped, trunc, ring_try, srv_try, preempted, requeued,
                 lost, up_last)
        return state, out

    if state is None:
        state = _init_state(J, L, K, Qcap)
    xs = (streams.n, streams.sizes, streams.durs)
    if faulted:
        xs = xs + (streams.up,)
    state, (qlen, occ, ndep) = jax.lax.scan(slot_step, state, xs)
    res = PolicyResult(qlen, occ, jnp.cumsum(ndep), state[13], state[14],
                       state[17], state[18], state[19])
    return (res, state) if return_state else res


def run_vqs_trace(streams: SchedStreams, *, J: int, L: int, K: int,
                  Qcap: int, A_max: int, engine: str = "scan",
                  work_steps: int | None = None,
                  drain: int | None = None,
                  window: int | None = None,
                  max_requeue: int = DEFAULT_MAX_REQUEUE,
                  strict: bool = False) -> PolicyResult:
    """Run one VQS simulation over explicit streams (random or trace).
    ``window`` is the Pallas kernel's VMEM time-window length (must divide
    the horizon; ignored by the other engines)."""
    if engine == "reference":
        return _run_vqs_reference_streams(streams, J=J, L=L, K=K, Qcap=Qcap,
                                          A_max=A_max,
                                          max_requeue=max_requeue)
    if engine == "scan":
        return run_vqs_streams(streams, J=J, L=L, K=K, Qcap=Qcap,
                               A_max=A_max, work_steps=work_steps,
                               drain=drain, max_requeue=max_requeue)
    if engine == "pallas":
        from repro.kernels.common import (ensemble_plane_bytes,
                                          pallas_precheck, resolve_windows)
        from repro.kernels.vqs.ops import vqs_simulate, vqs_vmem_bytes
        T, D = streams.n.shape[0], streams.durs.shape[-1]
        if not pallas_precheck(
                "vqs", nbytes=vqs_vmem_bytes(
                    J, L, K, Qcap, A_max, resolve_windows(T, window)[0]),
                hbm_bytes=ensemble_plane_bytes(
                    1, T, stream_lanes=1 + A_max + D, out_lanes=3),
                fault_plane=streams.up is not None, strict=strict):
            return run_vqs_streams(streams, J=J, L=L, K=K, Qcap=Qcap,
                                   A_max=A_max, work_steps=work_steps,
                                   drain=drain, max_requeue=max_requeue)
        batched = jax.tree.map(lambda x: x[None], streams)
        res = vqs_simulate(batched, J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
                           work_steps=work_steps, drain=drain, window=window)
        return jax.tree.map(lambda x: x[0], res)
    raise ValueError(f"unknown engine {engine!r}")


def run_vqs(key: jax.Array, lam: float, mu: float,
            sampler: Callable[[jax.Array, int], jax.Array],
            J: int = 4, L: int = 8, K: int = 16, Qcap: int = 512,
            A_max: int = 8, horizon: int = 10_000, engine: str = "scan",
            work_steps: int | None = None,
            drain: int | None = None,
            window: int | None = None,
            fault_rate: float = 0.0, repair_rate: float = 1.0,
            max_requeue: int = DEFAULT_MAX_REQUEUE,
            strict: bool = False) -> PolicyResult:
    """Simulate VQS on L unit-capacity servers for ``horizon`` slots.

    Randomness is always hoisted into ``make_streams`` (service durations
    attach to jobs at arrival — distributionally identical to the numpy
    engine's draw-at-placement for the memoryless service model).

    ``fault_rate > 0`` injects per-slot server capacity shocks: down
    servers evict their jobs into the tails of their virtual queues (up to
    ``max_requeue`` retries each, ``lost`` past that), identically on the
    scan and reference engines.
    """
    streams = make_streams(key, lam, mu, sampler, L=L, K=K, A_max=A_max,
                           horizon=horizon, fault_rate=fault_rate,
                           repair_rate=repair_rate)
    return run_vqs_trace(streams, J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
                         engine=engine, work_steps=work_steps, drain=drain,
                         window=window, max_requeue=max_requeue,
                         strict=strict)


def run_vqs_workload(workload, key: jax.Array, *, engine: str = "scan",
                     **config) -> PolicyResult:
    """Workload-first adapter: the registry entry behind
    ``run_policy(workload, policy="vqs", ...)``.  VQS partitions scalar
    sizes; vector workloads are rejected loudly."""
    workload.require_scalar("vqs")
    workload.check_sampler()
    return run_vqs(key, workload.lam, workload.mu, workload.sampler,
                   engine=engine, **config)


def monte_carlo_vqs_workload(workload, keys: jax.Array, *,
                             engine: str = "scan", **config) -> PolicyResult:
    """Workload-first adapter for ``monte_carlo_policy(policy="vqs")``."""
    workload.require_scalar("vqs")
    workload.check_sampler()
    return monte_carlo_vqs(keys, workload.lam, workload.mu,
                           workload.sampler, engine=engine, **config)


def monte_carlo_vqs(keys: jax.Array, lam: float, mu: float, sampler,
                    engine: str = "scan", work_steps: int | None = None,
                    drain: int | None = None, window: int | None = None,
                    J: int = 4, L: int = 8,
                    K: int = 16, Qcap: int = 512, A_max: int = 8,
                    horizon: int = 10_000, fault_rate: float = 0.0,
                    repair_rate: float = 1.0,
                    max_requeue: int = DEFAULT_MAX_REQUEUE,
                    strict: bool = False) -> PolicyResult:
    """One simulated cluster per key (vmap; "pallas" uses the kernel grid)."""
    if engine == "pallas":
        from repro.kernels.common import (ensemble_plane_bytes,
                                          pallas_precheck, resolve_windows)
        from repro.kernels.vqs.ops import vqs_simulate, vqs_vmem_bytes
        # keys is the LOCAL batch under a sharded mesh launch, so the
        # footprint check is per device (core.engine.sharding).
        G = int(keys.shape[0])
        if not pallas_precheck(
                "vqs", nbytes=vqs_vmem_bytes(
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
            return vqs_simulate(streams, J=J, L=L, K=K, Qcap=Qcap,
                                A_max=A_max, work_steps=work_steps,
                                drain=drain, window=window)
    fn = functools.partial(run_vqs, lam=lam, mu=mu, sampler=sampler,
                           engine=engine, work_steps=work_steps, drain=drain,
                           J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
                           horizon=horizon, fault_rate=fault_rate,
                           repair_rate=repair_rate, max_requeue=max_requeue)
    return jax.vmap(fn)(keys)
