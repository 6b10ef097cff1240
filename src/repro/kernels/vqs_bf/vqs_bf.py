"""Pallas TPU kernel: fused VQS-BF slot-step engine (DESIGN.md §13).

One program instance simulates one independent cluster of the Monte-Carlo
ensemble: the grid is ``(G, NW)`` — ensemble member x time window — and the
whole mutable simulation state (per-slot job sizes / departure slots / VQ
types, the 2J size-bucketed rings WITH their sequence-stamp plane, the
per-server ``(k_1, j*, k_{j*})`` configurations, the ``_empty`` membership
and the subscription matrix) lives in VMEM scratch that persists across the
sequentially-executed time windows of a member.

Servers sit on lanes: the job-slot planes are ``(K, L)``, the
subscriptions ``(2J, L)``, the configurations ``(5, L)`` rows, and every
per-server vector of a step (residual, masks, effective configuration) is
a ``(1, L)`` row — ``ceil(L / 128)`` vregs, where an ``(L, 1)`` column
would take ``ceil(L / 8)``.  Reductions over a server's job slots or VQs
run down the sublanes, reductions over servers along the lanes.  L needs
no padding: Mosaic masks the lanes past L of a partial vreg itself, so an
L that is not a multiple of 128 costs the tail of one vreg per row.

The serve pass is the branch-free one-placement-per-step work list of
``repro.core.engine.vqs_bf.run_vqs_bf_streams`` — staged (i)/(ii)/(iii)
largest-fit pops from the bucketed rings, shared max-weight renewal,
vectorized advance-past writes — transcribed with broadcasted-iota masks
and masked reductions in place of every dynamic index ("pop the largest
job <= residual" is a three-reduction lexicographic argmax over the
``(2J, Qcap)`` planes), a ``while_loop`` bounded at ``work_steps + 1`` steps
that stops after the first step with no placer (that step advances every
pending server, so later steps would be no-ops — the scan engine's exit,
plus at most one no-op step when nothing was visited).  The ``steps``
output counts the work steps each member ran over the horizon.
Each slot closes with the arrival-side BF-J pass: a loop over the slot's
arrivals (their lanes staged in SMEM by the push loop) offering every
still-queued arrival (identified by its surviving sequence stamp) to the
tightest feasible server.

Trajectories are bit-compatible with the scan engine (and, through it,
with the event-driven ``core/vqs_bf.py`` engine on trace streams) whenever
``truncated`` stays 0 — asserted by the interpret-mode parity tests in
tests/test_vqs_bf_engine.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import RES
from repro.core.partition import k_red
from repro.kernels.common import (arrival_spec, compiler_params,
                                  counter_spec, resolve_windows,
                                  slot_out_shape, slot_spec, tile_bytes,
                                  to_windows)

INF_SLOT = jnp.iinfo(jnp.int32).max
INF32 = jnp.iinfo(jnp.int32).max
CAP = RES


def vqs_bf_vmem_bytes(J: int, L: int, K: int, Qcap: int, A_max: int,
                      TW: int) -> int:
    """VMEM the fused VQS-BF kernel takes on the chip: the scratch state
    (three (K,L) server planes, THREE (2J,Qcap) bucket planes — effective
    size, duration, sequence stamp, one more than VQS for largest-fit-first
    FIFO tie-breaking — (2,2J) counts, (5,L) per-server block, (2J,L)
    subscription block), the double-buffered (C, 2J) configuration table,
    and the compiler's spills: three (K,L) planes and 16 (1,L) rows of the
    work list's per-server masks, and three (2J,Qcap) planes of the
    largest-fit pop.  Servers sit on lanes, so a per-server row pads to
    ``(8, ceil(L/128)*128)`` and L costs VMEM in 128-server steps; all
    planes padded to (8,128) tiles.  The per-window streams live in SMEM
    (``A_max`` and ``TW`` cost no VMEM).  The spill counts are fitted to
    the v5e compiler's allocation and kept honest by
    tests/test_tpu_compile.py."""
    del A_max, TW
    nvq = 2 * J
    return (6 * tile_bytes(K, L) + 6 * tile_bytes(nvq, Qcap)
            + tile_bytes(2, nvq) + tile_bytes(5, L) + tile_bytes(nvq, L)
            + 2 * tile_bytes(len(k_red(J)), nvq) + 16 * tile_bytes(1, L))


def _vqs_bf_kernel(n_ref, sizes_ref, durs_ref, confs_ref,
                   qlen_ref, occ_ref, ndep_ref, dropped_ref, trunc_ref,
                   steps_ref,
                   srv_ref, dep_ref, vqof_ref, reff_ref, rdur_ref, rseq_ref,
                   meta_ref, cfg_ref, want_ref, acc_ref, lane_ref,
                   *, J, L, K, Qcap, A_max, W, TW):
    w = pl.program_id(1)
    nvq = 2 * J
    C = confs_ref.shape[0]

    @pl.when(w == 0)
    def _init():
        srv_ref[...] = jnp.zeros((K, L), jnp.int32)
        dep_ref[...] = jnp.full((K, L), INF_SLOT, jnp.int32)
        vqof_ref[...] = jnp.full((K, L), -1, jnp.int32)
        reff_ref[...] = jnp.zeros((nvq, Qcap), jnp.int32)
        rdur_ref[...] = jnp.ones((nvq, Qcap), jnp.int32)
        rseq_ref[...] = jnp.zeros((nvq, Qcap), jnp.int32)
        meta_ref[...] = jnp.zeros((2, nvq), jnp.int32)  # qcnt row, seq_ctr
        row = jax.lax.broadcasted_iota(jnp.int32, (5, L), 0)
        # cfg_js = -1 (no active configuration); in_empty: all start empty
        cfg_ref[...] = jnp.where(row == 1, -1, jnp.where(row == 4, 1, 0))
        want_ref[...] = jnp.zeros((nvq, L), jnp.int32)
        acc_ref[0] = 0
        acc_ref[1] = 0
        acc_ref[2] = 0

    # servers on lanes: a per-server vector is a (1, L) row, the job slots
    # and the VQ subscriptions of every server are (K, L) / (2J, L) planes
    l_row = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    k_col = jax.lax.broadcasted_iota(jnp.int32, (K, 1), 0)
    j_row = jax.lax.broadcasted_iota(jnp.int32, (1, nvq), 1)
    j_col = jax.lax.broadcasted_iota(jnp.int32, (nvq, 1), 0)
    q_jq = jax.lax.broadcasted_iota(jnp.int32, (nvq, Qcap), 1)
    j_jq = jax.lax.broadcasted_iota(jnp.int32, (nvq, Qcap), 0)
    c_col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    c_flat = jax.lax.broadcasted_iota(jnp.int32, (C, nvq), 0)
    diag = j_col == j_row                                      # (nvq, nvq)
    confs = confs_ref[...]

    def kfree_of(rowmask, srv):
        """Lowest empty job slot of the server ``rowmask`` selects."""
        row_srv = jnp.sum(jnp.where(rowmask, srv, 0), axis=1,
                          keepdims=True)                       # (K, 1)
        kfree = jnp.min(jnp.where(row_srv == 0, k_col, K))
        return kfree, kfree < K

    def slot_step(tt, carry):
        dropped, trunc, steps = carry
        t = w * TW + tt

        # 1. departures
        dep = dep_ref[...]
        srv = srv_ref[...]
        vqof = vqof_ref[...]
        leaving = dep == t
        freed = leaving.any(axis=0, keepdims=True)            # (1, L)
        n_dep = leaving.sum()
        srv = jnp.where(leaving, 0, srv)
        vqof = jnp.where(leaving, -1, vqof)
        srv_ref[...] = srv
        vqof_ref[...] = vqof
        dep_ref[...] = jnp.where(leaving, INF_SLOT, dep)
        empty_now = (srv > 0).sum(axis=0, keepdims=True) == 0  # (1, L)

        # 2. arrivals: classify on the grid, push to first-empty bucket
        # slots with fresh sequence stamps (lane order == push order); each
        # arrival's (vq, pos, seq, eff, dur, landed) row goes to SMEM for
        # the BF-J pass of step 5.  Lanes past n_t are no-ops, so the loop
        # stops at n_t.
        n_t = n_ref[0, tt]
        meta = meta_ref[...]
        seq_ctr = meta[1, 0]

        def push(a, pcarry):
            qcnt, dropped, arrived = pcarry
            g = jnp.maximum(jnp.round(sizes_ref[0, tt, a] * RES),
                            1.0).astype(jnp.int32)
            m_h = jnp.int32(0)
            for kk in range(1, J + 1):
                m_h = m_h + (g <= (RES >> kk)).astype(jnp.int32)
            m_h = jnp.minimum(m_h, J - 1)
            upper = jnp.right_shift(jnp.int32(RES), m_h)
            vq_a = jnp.where(3 * g > 2 * upper, 2 * m_h, 2 * m_h + 1)
            vq_a = jnp.where(g <= (RES >> J), nvq - 1, vq_a)
            eff_a = jnp.where(vq_a == nvq - 1, jnp.maximum(g, RES >> J), g)
            dur_a = durs_ref[0, tt, a]
            seq_a = seq_ctr + a
            reff = reff_ref[...]
            emp_row = (j_jq == vq_a) & (reff == 0)             # (nvq, Qcap)
            pos = jnp.min(jnp.where(emp_row, q_jq, Qcap))
            land = pos < Qcap
            wm = (j_jq == vq_a) & (q_jq == pos) & land
            reff_ref[...] = jnp.where(wm, eff_a, reff)
            rdur_ref[...] = jnp.where(wm, dur_a, rdur_ref[...])
            rseq_ref[...] = jnp.where(wm, seq_a, rseq_ref[...])
            for i, v in enumerate((vq_a, pos, seq_a, eff_a, dur_a,
                                   land.astype(jnp.int32))):
                lane_ref[i, a] = v
            return (qcnt + jnp.where((j_row == vq_a) & land, 1, 0),
                    dropped + jnp.where(land, 0, 1),
                    arrived | (j_col == vq_a).astype(jnp.int32))

        qcnt, dropped, arrived = jax.lax.fori_loop(
            0, n_t, push,
            (meta[0:1], dropped, jnp.zeros((nvq, 1), jnp.int32)))
        arrived = arrived != 0                                 # (nvq, 1)
        meta_ref[0:1, :] = qcnt
        meta_ref[1:2, :] = jnp.where(j_row == 0, seq_ctr + A_max, meta[1:2])

        # 3. visit set
        want = want_ref[...] != 0                              # (nvq, L)
        woken = (want & arrived).any(axis=0, keepdims=True)
        want_ref[...] = (want & ~arrived).astype(jnp.int32)
        has_cfg0 = cfg_ref[3:4, :] != 0                        # (1, L)
        in_empty0 = cfg_ref[4:5, :] != 0
        visit = freed | woken | (in_empty0 & (qcnt.sum() > 0))
        renew_needed = visit & (empty_now | ~has_cfg0)

        # 4. work list: at most W+1 one-placement steps, each the scan
        # engine's masked-select step verbatim; a step with no placer
        # advances every pending server, so the loop stops after it
        def work(wcarry):
            # (1, L) masks ride the loop as int32: Mosaic cannot carry
            # bool vectors across loop iterations
            step, touched, advanced, trunc, _ = wcarry
            touched, advanced = touched != 0, advanced != 0
            qcnt = meta_ref[0:1, :]
            reff = reff_ref[...]
            rdur = rdur_ref[...]
            rseq = rseq_ref[...]
            srv = srv_ref[...]
            vqof = vqof_ref[...]
            cfg_k1 = cfg_ref[0:1, :] != 0                      # (1, L)
            cfg_js = cfg_ref[1:2, :]
            cfg_ks = cfg_ref[2:3, :]
            has_cfg = cfg_ref[3:4, :] != 0
            in_empty = cfg_ref[4:5, :] != 0
            want = want_ref[...] != 0

            pending = visit & ~advanced
            hx = qcnt > 0                                      # (1, nvq)
            hx_col = jnp.sum(jnp.where(diag, qcnt, 0), axis=1,
                             keepdims=True) > 0                # (nvq, 1)
            occ_ring = reff > 0
            row_min = jnp.min(jnp.where(occ_ring, reff, INF32), axis=1,
                              keepdims=True)                   # (nvq, 1)
            glob_min = row_min.min()

            # shared max-weight renewal candidate (first-index argmax)
            w_c = jnp.sum(confs * qcnt, axis=1)                # (C,)
            ci = jnp.min(jnp.where(w_c == w_c.max(), c_flat[:, 0], C))
            row = jnp.sum(jnp.where(c_col == ci, confs, 0),
                          axis=0)[None, :]                     # (1, nvq)
            r_k1 = jnp.sum(jnp.where(j_row == 1, row, 0)) > 0
            r_js = jnp.min(jnp.where((row > 0) & (j_row != 1), j_row, nvq))
            r_js = jnp.where(r_js == nvq, -1, r_js)
            r_ks = jnp.sum(jnp.where(j_row == jnp.maximum(r_js, 0), row, 0))
            r_ks = jnp.where(r_js >= 0, r_ks, 0)
            ren = renew_needed & ~touched
            # bool selects as logic: Mosaic has no select of i1 vectors
            eff_k1 = (ren & r_k1) | (~ren & cfg_k1)
            eff_js = jnp.where(ren, r_js, cfg_js)              # (1, L)
            eff_ks = jnp.where(ren, r_ks, cfg_ks)

            live = srv > 0                                     # (K, L)
            occ = srv.sum(axis=0, keepdims=True)
            resid = CAP - occ
            has_vq1 = ((vqof == 1) & live).any(axis=0, keepdims=True)
            js_oh = eff_js == j_col                            # (nvq, L)
            js_min = jnp.min(jnp.where(js_oh, row_min, INF32),
                             axis=0, keepdims=True)
            js_ex = (js_oh & hx_col).any(axis=0, keepdims=True)
            cnt_js = ((vqof == eff_js) & live).sum(axis=0, keepdims=True)
            rm1 = jnp.min(jnp.where(j_col == 1, row_min, INF32))

            k1_can = eff_k1 & ~has_vq1 & (rm1 <= resid)
            js_can = (eff_js >= 0) & (cnt_js < eff_ks) & (js_min <= resid)
            any_can = glob_min <= resid
            would = pending & (k1_can | js_can | any_can)

            placer = jnp.min(jnp.where(would, l_row, L))
            tch = pending & (l_row <= placer)
            adv = pending & (l_row < placer)
            do_ren = tch & ren
            new_k1 = (do_ren & r_k1) | (~do_ren & cfg_k1)
            new_js = jnp.where(do_ren, r_js, cfg_js)
            new_ks = jnp.where(do_ren, r_ks, cfg_ks)
            new_has = has_cfg | tch
            # first touch only — see engine/vqs.py (stale empty_now mask)
            new_empty = in_empty | (tch & ~touched & empty_now)
            touched = touched | tch
            advanced = advanced | adv

            sub1 = adv & eff_k1 & ~has_vq1 & ~(hx & (j_row == 1)).any()
            subj = adv & (eff_js >= 0) & (cnt_js < eff_ks) & ~js_ex
            want = want | (sub1 & (j_col == 1)) | (subj & js_oh)
            want_ref[...] = want.astype(jnp.int32)

            # serve the placer: ONE staged (i)/(ii)/(iii) largest-fit pop
            any_p = placer < L
            rowmask = l_row == placer                          # (1, L)
            do1 = (rowmask & k1_can).any()
            doj = ~do1 & (rowmask & js_can).any()
            jsx_s = jnp.maximum(jnp.max(jnp.where(rowmask, eff_js, -1)), 0)
            rowsel = (do1 & (j_jq == 1)) | (~do1 & doj & (j_jq == jsx_s)) \
                | (~do1 & ~doj)
            resid_s = jnp.max(jnp.where(rowmask, resid, -1))
            elig = occ_ring & rowsel & (reff <= resid_s)
            best_eff = jnp.max(jnp.where(elig, reff, 0))
            cand = elig & (reff == best_eff)
            vq_p = jnp.min(jnp.where(cand, j_jq, nvq))         # lowest VQ
            found = vq_p < nvq
            row_cand = cand & (j_jq == vq_p)
            best_seq = jnp.min(jnp.where(row_cand, rseq, INF32))
            entry = row_cand & (rseq == best_seq)              # FIFO tie
            pos_p = jnp.min(jnp.where(entry, q_jq, Qcap))
            pm = (j_jq == vq_p) & (q_jq == pos_p)
            eff_p = jnp.sum(jnp.where(pm, reff, 0))
            dur_p = jnp.sum(jnp.where(pm, rdur, 0))
            do_place = any_p & found

            kfree, ok = kfree_of(rowmask, srv)
            lk = rowmask & (k_col == kfree) & ok & do_place    # (K, L)
            srv_ref[...] = jnp.where(lk, eff_p, srv)
            dep_ref[...] = jnp.where(lk, t + dur_p, dep_ref[...])
            vqof_ref[...] = jnp.where(lk, vq_p, vqof)
            reff_ref[...] = jnp.where(pm & do_place, 0, reff)
            meta_ref[0:1, :] = qcnt - jnp.where((j_row == vq_p) & do_place, 1,
                                                0)
            trunc = trunc + (do_place & ~ok).astype(jnp.int32)  # K-overflow
            new_empty = new_empty & ~(rowmask & do_place)
            cfg_ref[...] = jnp.concatenate(
                [new_k1.astype(jnp.int32), new_js, new_ks,
                 new_has.astype(jnp.int32), new_empty.astype(jnp.int32)],
                axis=0)
            return (step + 1, touched.astype(jnp.int32),
                    advanced.astype(jnp.int32), trunc, ~any_p)

        zero_row = jnp.zeros((1, L), jnp.int32)
        n_steps, _, advanced, trunc, _ = jax.lax.while_loop(
            lambda c: (c[0] < W + 1) & jnp.logical_not(c[-1]), work,
            (jnp.int32(0), zero_row, zero_row, trunc, jnp.bool_(False)))
        steps = steps + n_steps
        # bound hit with servers still unserved: slot finished lazily
        trunc = trunc + (visit & (advanced == 0)).any().astype(jnp.int32)

        # 5. arrival-side BF-J pass: each still-queued arrival (sequence
        # stamp survived the serve pass) to the tightest feasible server
        def bfj(a, trunc):
            vq_a, pos_a, seq_a, eff_a, dur_a, land = (
                lane_ref[i, a] for i in range(6))
            reff = reff_ref[...]
            rseq = rseq_ref[...]
            srv = srv_ref[...]
            em = (j_jq == vq_a) & (q_jq == pos_a)
            queued = (land != 0) & (jnp.sum(jnp.where(em, reff, 0)) > 0) \
                & (jnp.sum(jnp.where(em, rseq, 0)) == seq_a)
            resid = CAP - srv.sum(axis=0, keepdims=True)       # (1, L)
            candm = resid >= eff_a
            rbest = jnp.min(jnp.where(candm, resid, INF32))
            s = jnp.min(jnp.where(candm & (resid == rbest), l_row, L))
            do = queued & (s < L)
            rowmask = l_row == s
            kfree, ok = kfree_of(rowmask, srv)
            lk = rowmask & (k_col == kfree) & ok & do
            srv_ref[...] = jnp.where(lk, eff_a, srv)
            dep_ref[...] = jnp.where(lk, t + dur_a, dep_ref[...])
            vqof_ref[...] = jnp.where(lk, vq_a, vqof_ref[...])
            reff_ref[...] = jnp.where(em & do, 0, reff)
            meta_ref[0:1, :] = meta_ref[0:1, :] \
                - jnp.where((j_row == vq_a) & do, 1, 0)
            in_empty = (cfg_ref[4:5, :] != 0) & ~(rowmask & do)
            cfg_ref[4:5, :] = in_empty.astype(jnp.int32)
            return trunc + (do & ~ok).astype(jnp.int32)

        trunc = jax.lax.fori_loop(0, n_t, bfj, trunc)

        qlen_ref[0, tt] = meta_ref[0:1, :].sum()
        occ_ref[0, tt] = srv_ref[...].sum().astype(jnp.float32) / RES
        ndep_ref[0, tt] = n_dep.astype(jnp.int32)
        return dropped, trunc, steps

    dropped, trunc, steps = jax.lax.fori_loop(
        0, TW, slot_step, (acc_ref[0], acc_ref[1], acc_ref[2]))
    acc_ref[0] = dropped
    acc_ref[1] = trunc
    acc_ref[2] = steps
    dropped_ref[0, 0] = dropped
    trunc_ref[0, 0] = trunc
    steps_ref[0, 0] = steps


@functools.partial(
    jax.jit,
    static_argnames=("J", "L", "K", "Qcap", "A_max", "work_steps", "window",
                     "interpret"))
def vqs_bf_pallas(n: jax.Array, sizes: jax.Array, durs: jax.Array,
                  J: int, L: int, K: int, Qcap: int, A_max: int,
                  work_steps: int, window: int | None = None,
                  interpret: bool = False):
    """Run the fused VQS-BF slot engine on an ensemble of clusters.

    n (G, T) int32, sizes (G, T, A_max) f32, durs (G, T, D) int32 with the
    per-arrival durations in the last A_max lanes — one pre-generated
    stream set per ensemble member (only those lanes are streamed into
    the kernel).  Returns per-slot (queue_len, occupancy, departures) of
    shape (G, T) plus (dropped, truncated, steps) of shape (G,), ``steps``
    being the work-list steps each member ran over the horizon (at most
    ``T * (work_steps + 1)``).  ``window`` splits the horizon into
    VMEM-sized chunks exactly as for the VQS kernel (must divide T)."""
    from repro.core.engine.ops import k_red_jnp

    G, T = n.shape
    TW, NW = resolve_windows(T, window)
    D = durs.shape[-1]
    confs = k_red_jnp(J)
    C = confs.shape[0]
    nvq = 2 * J
    kernel = functools.partial(
        _vqs_bf_kernel, J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
        W=work_steps, TW=TW)
    qlen, occ, ndep, dropped, trunc, steps = pl.pallas_call(
        kernel,
        grid=(G, NW),
        out_shape=(slot_out_shape(G, T, TW, jnp.int32),
                   slot_out_shape(G, T, TW, jnp.float32),
                   slot_out_shape(G, T, TW, jnp.int32),
                   jax.ShapeDtypeStruct((G, 1, 1), jnp.int32),
                   jax.ShapeDtypeStruct((G, 1, 1), jnp.int32),
                   jax.ShapeDtypeStruct((G, 1, 1), jnp.int32)),
        in_specs=[slot_spec(TW),
                  arrival_spec(TW, A_max), arrival_spec(TW, A_max),
                  pl.BlockSpec((C, nvq), lambda g, w: (0, 0))],
        out_specs=(slot_spec(TW), slot_spec(TW), slot_spec(TW),
                   counter_spec(), counter_spec(), counter_spec()),
        scratch_shapes=[pltpu.VMEM((K, L), jnp.int32),
                        pltpu.VMEM((K, L), jnp.int32),
                        pltpu.VMEM((K, L), jnp.int32),
                        pltpu.VMEM((nvq, Qcap), jnp.int32),
                        pltpu.VMEM((nvq, Qcap), jnp.int32),
                        pltpu.VMEM((nvq, Qcap), jnp.int32),
                        pltpu.VMEM((2, nvq), jnp.int32),
                        pltpu.VMEM((5, L), jnp.int32),
                        pltpu.VMEM((nvq, L), jnp.int32),
                        pltpu.SMEM((3,), jnp.int32),
                        pltpu.SMEM((6, A_max), jnp.int32)],
        compiler_params=compiler_params(
            vqs_bf_vmem_bytes(J, L, K, Qcap, A_max, TW)),
        interpret=interpret,
    )(to_windows(n, TW), sizes, durs[..., D - A_max:], confs)
    return (qlen.reshape(G, T), occ.reshape(G, T), ndep.reshape(G, T),
            dropped[:, 0, 0], trunc[:, 0, 0], steps[:, 0, 0])
