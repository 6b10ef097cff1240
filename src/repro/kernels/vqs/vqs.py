"""Pallas TPU kernel: fused VQS slot-step engine (DESIGN.md §6).

One program instance simulates one independent cluster of the Monte-Carlo
ensemble: the grid is ``(G, NW)`` — ensemble member x time window — and the
whole mutable simulation state (per-slot job sizes / departure slots / VQ
types, the 2J virtual-queue rings, per-server active configurations, the
``_empty`` membership and the subscription matrix) lives in VMEM scratch
that persists across the sequentially-executed time windows of a member.

Every slot step (departures -> classify + ring-push arrivals -> visit-set ->
bounded serve work list) runs inside the kernel with no HBM round-trips;
only the pre-generated randomness streams are streamed in per window and
only the per-slot outputs (queue length, occupancy, departures) stream out.

The serve pass is the branch-free work list of
``repro.core.engine.vqs.run_vqs_streams`` (advance past non-placing visited
servers under the shared max-weight renewal, then prefix-fit-pack the first
placer) transcribed with broadcasted-iota masks and reductions in place of
every dynamic index, unrolled to the fixed ``work_steps + 1`` bound (the
kernel pays the bound; the host scan engine early-exits — same trajectory).
Trajectories are bit-compatible with the scan engine (and, through it, with
the event-driven numpy engine on trace streams) whenever ``truncated`` stays
0 — asserted by the interpret-mode parity tests in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import RES, TWO_THIRDS
from repro.core.partition import k_red
from repro.kernels.common import (LANES, compiler_params, counter_spec,
                                  prefix_sum, resolve_windows, slot_out_shape,
                                  slot_spec, stream_block_bytes, tile_bytes,
                                  to_windows)

INF_SLOT = jnp.iinfo(jnp.int32).max
CAP = RES
RESERVE = TWO_THIRDS


def vqs_vmem_bytes(J: int, L: int, K: int, Qcap: int, A_max: int,
                   TW: int) -> int:
    """VMEM the fused VQS kernel takes on the chip: the scratch state
    (three (L,K) planes, two (2J,Qcap) ring planes, (2,2J) ring heads,
    (4,L) per-server block, (L,2J) placer block), the double-buffered
    (TW, A_max) size and duration blocks and (C, 2J) configuration table,
    and the compiler's spills — 23 (L,128) planes of the work list's
    per-server masks and two (2J,Qcap) planes.  All padded to (8,128)
    tiles.  The spill counts are fitted to the v5e compiler's allocation
    and kept honest by tests/test_tpu_compile.py."""
    nvq = 2 * J
    return (3 * tile_bytes(L, K) + 2 * tile_bytes(nvq, Qcap)
            + tile_bytes(2, nvq) + tile_bytes(4, L) + tile_bytes(L, nvq)
            + stream_block_bytes(TW, A_max, A_max)
            + 2 * tile_bytes(len(k_red(J)), nvq)
            + 23 * tile_bytes(L, LANES) + 2 * tile_bytes(nvq, Qcap))


def _vqs_kernel(n_ref, sizes_ref, durs_ref, confs_ref,
                qlen_ref, occ_ref, ndep_ref, dropped_ref, trunc_ref,
                srv_ref, dep_ref, vqof_ref, reff_ref, rdur_ref,
                hq_ref, cfg_ref, want_ref, acc_ref,
                *, J, L, K, Qcap, A_max, W, P, TW):
    w = pl.program_id(1)
    nvq = 2 * J
    C = confs_ref.shape[0]

    @pl.when(w == 0)
    def _init():
        srv_ref[...] = jnp.zeros((L, K), jnp.int32)
        dep_ref[...] = jnp.full((L, K), INF_SLOT, jnp.int32)
        vqof_ref[...] = jnp.full((L, K), -1, jnp.int32)
        reff_ref[...] = jnp.zeros((nvq, Qcap), jnp.int32)
        rdur_ref[...] = jnp.ones((nvq, Qcap), jnp.int32)
        hq_ref[...] = jnp.zeros((2, nvq), jnp.int32)
        row = jax.lax.broadcasted_iota(jnp.int32, (4, L), 0)
        # cfg_js = -1 (no active configuration); in_empty: all start empty
        cfg_ref[...] = jnp.where(row == 1, -1, jnp.where(row == 3, 1, 0))
        want_ref[...] = jnp.zeros((L, nvq), jnp.int32)
        acc_ref[0] = 0
        acc_ref[1] = 0

    l_col = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    j_row = jax.lax.broadcasted_iota(jnp.int32, (1, nvq), 1)
    q_jq = jax.lax.broadcasted_iota(jnp.int32, (nvq, Qcap), 1)
    j_jq = jax.lax.broadcasted_iota(jnp.int32, (nvq, Qcap), 0)
    p_row = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    q_pq = jax.lax.broadcasted_iota(jnp.int32, (P, Qcap), 1)
    c_col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    c_flat = jax.lax.broadcasted_iota(jnp.int32, (C, nvq), 0)
    confs = confs_ref[...]

    def slot_step(tt, carry):
        dropped, trunc = carry
        t = w * TW + tt

        # 1. departures
        dep = dep_ref[...]
        srv = srv_ref[...]
        vqof = vqof_ref[...]
        leaving = dep == t
        freed = leaving.any(axis=1, keepdims=True)            # (L, 1)
        n_dep = leaving.sum()
        srv = jnp.where(leaving, 0, srv)
        vqof = jnp.where(leaving, -1, vqof)
        srv_ref[...] = srv
        vqof_ref[...] = vqof
        dep_ref[...] = jnp.where(leaving, INF_SLOT, dep)
        empty_now = (srv > 0).sum(axis=1, keepdims=True) == 0  # (L, 1)

        # 2. arrivals: classify on the integer grid, push to ring tails
        n_t = n_ref[0, tt]
        hq = hq_ref[...]
        head, qcnt = hq[0:1], hq[1:2]                          # (1, nvq)
        reff = reff_ref[...]
        rdur = rdur_ref[...]
        arrived = jnp.zeros((1, nvq), bool)
        for a in range(A_max):
            valid = a < n_t
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
            oh = j_row == vq_a                                 # (1, nvq)
            cnt_a = jnp.sum(jnp.where(oh, qcnt, 0))
            head_a = jnp.sum(jnp.where(oh, head, 0))
            land = valid & (cnt_a < Qcap)
            pos = jnp.remainder(head_a + cnt_a, Qcap)
            wm = (j_jq == vq_a) & (q_jq == pos) & land         # (nvq, Qcap)
            reff = jnp.where(wm, eff_a, reff)
            rdur = jnp.where(wm, durs_ref[0, tt, a], rdur)
            qcnt = qcnt + jnp.where(oh & land, 1, 0)
            dropped = dropped + jnp.where(valid & ~land, 1, 0)
            arrived = arrived | (oh & valid)
        reff_ref[...] = reff
        rdur_ref[...] = rdur
        hq_ref[...] = jnp.concatenate([head, qcnt], axis=0)

        # 3. visit set
        want = want_ref[...] != 0                              # (L, nvq)
        woken = (want & arrived).any(axis=1, keepdims=True)
        want_ref[...] = (want & ~arrived).astype(jnp.int32)
        cfgm = cfg_ref[...]
        has_cfg0 = (cfgm[2:3].T != 0)                          # (L, 1)
        in_empty0 = (cfgm[3:4].T != 0)
        visit = freed | woken | (in_empty0 & (qcnt.sum() > 0))
        renew_needed = visit & (empty_now | ~has_cfg0)

        # 4. work list: W placement steps + 1 drain pass (fixed unroll —
        # each iteration is the scan engine's masked-select step verbatim)
        def work(_, wcarry):
            # (L, 1) masks ride the loop as int32: Mosaic cannot carry
            # bool vectors across loop iterations
            touched, advanced, trunc = wcarry
            touched, advanced = touched != 0, advanced != 0
            hq = hq_ref[...]
            head, qcnt = hq[0:1], hq[1:2]
            reff = reff_ref[...]
            rdur = rdur_ref[...]
            srv = srv_ref[...]
            vqof = vqof_ref[...]
            cfgm = cfg_ref[...]
            cfg_k1 = (cfgm[0:1].T != 0)                        # (L, 1)
            cfg_js = cfgm[1:2].T
            has_cfg = (cfgm[2:3].T != 0)
            in_empty = (cfgm[3:4].T != 0)
            want = want_ref[...] != 0

            pending = visit & ~advanced
            hx = qcnt > 0
            hmask = q_jq == jnp.remainder(head, Qcap).T        # (nvq, Qcap)
            head_effs = jnp.sum(jnp.where(hmask, reff, 0), axis=1)[None, :]

            # shared max-weight renewal candidate (first-index argmax)
            w_c = jnp.sum(confs * qcnt, axis=1)                # (C,)
            ci = jnp.min(jnp.where(w_c == w_c.max(),
                                   c_flat[:, 0], C))
            row = jnp.sum(jnp.where(c_col == ci, confs, 0),
                          axis=0)[None, :]                     # (1, nvq)
            r_k1 = jnp.sum(jnp.where(j_row == 1, row, 0)) > 0
            r_js = jnp.min(jnp.where((row > 0) & (j_row != 1), j_row, nvq))
            r_js = jnp.where(r_js == nvq, -1, r_js)
            ren = renew_needed & ~touched
            # bool selects as logic: Mosaic has no select of i1 vectors
            eff_k1 = (ren & r_k1) | (~ren & cfg_k1)
            eff_js = jnp.where(ren, r_js, cfg_js)              # (L, 1)

            occ = srv.sum(axis=1, keepdims=True)
            is1 = (vqof == 1) & (srv > 0)
            vq1_occ = jnp.where(is1, srv, 0).sum(axis=1, keepdims=True)
            has_vq1 = is1.any(axis=1, keepdims=True)
            resid = CAP - occ
            other_occ = occ - vq1_occ
            other_cap = jnp.where(eff_k1, CAP - RESERVE, CAP)
            ex1 = (hx & (j_row == 1)).any()
            he1 = jnp.sum(jnp.where(j_row == 1, head_effs, 0))
            k1_can = eff_k1 & ~has_vq1 & ex1 & (he1 <= resid)
            js_oh = eff_js == j_row                            # (L, nvq)
            js_head = jnp.sum(jnp.where(js_oh, head_effs, 0),
                              axis=1, keepdims=True)
            js_ex = (js_oh & hx).any(axis=1, keepdims=True)
            js_can = (eff_js >= 0) & js_ex \
                & (other_occ + js_head <= other_cap)
            would = pending & (k1_can | js_can)

            placer = jnp.min(jnp.where(would, l_col, L))
            tch = pending & (l_col <= placer)
            adv = pending & (l_col < placer)
            do_ren = tch & ren
            new_k1 = (do_ren & r_k1) | (~do_ren & cfg_k1)
            new_js = jnp.where(do_ren, r_js, cfg_js)
            new_has = has_cfg | tch
            # first touch only — see engine/vqs.py (stale empty_now mask)
            new_empty = in_empty | (tch & ~touched & empty_now)
            touched = touched | tch
            advanced = advanced | adv

            sub1 = adv & eff_k1 & ~has_vq1 & ~ex1
            subj = adv & (eff_js >= 0) & ~js_ex
            want = want | (sub1 & (j_row == 1)) | (subj & js_oh)
            want_ref[...] = want.astype(jnp.int32)

            # serve the placer: 1 reserved VQ_1 job or a prefix-fit batch
            any_p = placer < L
            rowmask = l_col == placer                          # (L, 1)
            do_k1 = (rowmask & k1_can).any()
            js_s = jnp.max(jnp.where(rowmask, eff_js, -1))
            j_sel = jnp.where(do_k1, 1, jnp.maximum(js_s, 0))
            head_sel = jnp.sum(jnp.where(j_row == j_sel, head, 0))
            qcnt_sel = jnp.sum(jnp.where(j_row == j_sel, qcnt, 0))
            rrow_e = jnp.sum(jnp.where(j_jq == j_sel, reff, 0),
                             axis=0)[None, :]                  # (1, Qcap)
            rrow_d = jnp.sum(jnp.where(j_jq == j_sel, rdur, 0),
                             axis=0)[None, :]
            wsel = q_pq == jnp.remainder(head_sel + p_row, Qcap).T  # (P, Qcap)
            effs_w = jnp.sum(jnp.where(wsel, rrow_e, 0), axis=1)[None, :]
            durs_w = jnp.sum(jnp.where(wsel, rrow_d, 0), axis=1)[None, :]
            in_q = p_row < qcnt_sel
            budget = jnp.max(jnp.where(rowmask, other_cap - other_occ, -1))
            fit = in_q & (prefix_sum(effs_w) <= budget)
            m = jnp.where(do_k1, 1, fit.sum())
            m = jnp.where(any_p, m, 0)

            row_srv = jnp.sum(jnp.where(rowmask, srv, 0),
                              axis=0)[None, :]                 # (1, K)
            es = row_srv == 0
            free_cnt = es.sum()
            slotrank = prefix_sum(es) - 1
            sel = (row_srv.T == 0) & (slotrank.T == p_row) \
                & (p_row < m)                                  # (K, P)
            val_k = jnp.sum(jnp.where(sel, effs_w, 0), axis=1)[None, :]
            dur_k = jnp.sum(jnp.where(sel, durs_w, 0), axis=1)[None, :]
            placed_k = sel.any(axis=1)[None, :]                # (1, K)
            lk = rowmask & placed_k                            # (L, K)
            srv_ref[...] = jnp.where(lk, val_k, srv)
            dep_ref[...] = jnp.where(lk, t + dur_k, dep_ref[...])
            vqof_ref[...] = jnp.where(lk, j_sel, vqof)
            dm = jnp.where((j_row == j_sel) & any_p, m, 0)
            hq_ref[...] = jnp.concatenate([head + dm, qcnt - dm], axis=0)
            new_empty = new_empty & ~(rowmask & (m > 0))
            cfg_ref[...] = jnp.concatenate(
                [new_k1.astype(jnp.int32).T, new_js.T,
                 new_has.astype(jnp.int32).T, new_empty.astype(jnp.int32).T],
                axis=0)
            trunc = trunc + jnp.maximum(m - free_cnt, 0)       # K-overflow
            return (touched.astype(jnp.int32), advanced.astype(jnp.int32),
                    trunc)

        zero_col = jnp.zeros((L, 1), jnp.int32)
        _, advanced, trunc = jax.lax.fori_loop(
            0, W + 1, work, (zero_col, zero_col, trunc))
        # bound hit with servers still unserved: slot finished lazily
        trunc = trunc + (visit & (advanced == 0)).any().astype(jnp.int32)

        qcnt = hq_ref[1:2, :]
        qlen_ref[0, tt] = qcnt.sum()
        occ_ref[0, tt] = srv_ref[...].sum().astype(jnp.float32) / RES
        ndep_ref[0, tt] = n_dep.astype(jnp.int32)
        return dropped, trunc

    dropped, trunc = jax.lax.fori_loop(
        0, TW, slot_step, (acc_ref[0], acc_ref[1]))
    acc_ref[0] = dropped
    acc_ref[1] = trunc
    dropped_ref[0, 0] = dropped
    trunc_ref[0, 0] = trunc


@functools.partial(
    jax.jit,
    static_argnames=("J", "L", "K", "Qcap", "A_max", "work_steps", "drain",
                     "window", "interpret"))
def vqs_pallas(n: jax.Array, sizes: jax.Array, durs: jax.Array,
               J: int, L: int, K: int, Qcap: int, A_max: int,
               work_steps: int, drain: int, window: int | None = None,
               interpret: bool = False):
    """Run the fused VQS slot engine on an ensemble of clusters.

    n (G, T) int32, sizes (G, T, A_max) f32, durs (G, T, D) int32 with the
    per-arrival durations in the last A_max lanes (D = L*K+A_max for
    make_streams, D = A_max for streams_from_trace) — one pre-generated
    stream set per ensemble member.  Only those last A_max lanes are
    streamed into the kernel.  Returns per-slot (queue_len,
    occupancy, departures) of shape (G, T) plus (dropped, truncated) of
    shape (G,).

    ``window`` splits the horizon into VMEM-sized chunks: the grid is
    (G, T//window) and simulation state persists in scratch across a
    member's sequentially-executed windows.  Must divide T (default: whole
    horizon in one window).
    """
    from repro.core.engine.ops import k_red_jnp

    G, T = n.shape
    TW, NW = resolve_windows(T, window)
    D = durs.shape[-1]
    confs = k_red_jnp(J)
    C = confs.shape[0]
    nvq = 2 * J
    kernel = functools.partial(
        _vqs_kernel, J=J, L=L, K=K, Qcap=Qcap, A_max=A_max,
        W=work_steps, P=drain, TW=TW)
    qlen, occ, ndep, dropped, trunc = pl.pallas_call(
        kernel,
        grid=(G, NW),
        out_shape=(slot_out_shape(G, T, TW, jnp.int32),
                   slot_out_shape(G, T, TW, jnp.float32),
                   slot_out_shape(G, T, TW, jnp.int32),
                   jax.ShapeDtypeStruct((G, 1, 1), jnp.int32),
                   jax.ShapeDtypeStruct((G, 1, 1), jnp.int32)),
        in_specs=[slot_spec(TW),
                  pl.BlockSpec((1, TW, A_max), lambda g, w: (g, w, 0)),
                  pl.BlockSpec((1, TW, A_max), lambda g, w: (g, w, 0)),
                  pl.BlockSpec((C, nvq), lambda g, w: (0, 0))],
        out_specs=(slot_spec(TW), slot_spec(TW), slot_spec(TW),
                   counter_spec(), counter_spec()),
        scratch_shapes=[pltpu.VMEM((L, K), jnp.int32),
                        pltpu.VMEM((L, K), jnp.int32),
                        pltpu.VMEM((L, K), jnp.int32),
                        pltpu.VMEM((nvq, Qcap), jnp.int32),
                        pltpu.VMEM((nvq, Qcap), jnp.int32),
                        pltpu.VMEM((2, nvq), jnp.int32),
                        pltpu.VMEM((4, L), jnp.int32),
                        pltpu.VMEM((L, nvq), jnp.int32),
                        pltpu.SMEM((2,), jnp.int32)],
        compiler_params=compiler_params(
            vqs_vmem_bytes(J, L, K, Qcap, A_max, TW)),
        interpret=interpret,
    )(to_windows(n, TW), sizes, durs[..., D - A_max:], confs)
    return (qlen.reshape(G, T), occ.reshape(G, T), ndep.reshape(G, T),
            dropped[:, 0, 0], trunc[:, 0, 0])
