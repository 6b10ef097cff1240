"""Pallas TPU kernel: fused multi-resource BF-J/S slot-step engine
(DESIGN.md §8).

One program instance simulates one independent cluster of the Monte-Carlo
ensemble: the grid is ``(G, NW)`` — ensemble member x time window — and the
whole mutable simulation state (the ``(L, K, R)`` per-slot demand vectors,
departure slots, the ``(Qcap, R)`` queued-demand buffer with its
duration/seq metadata and the running counters) lives in VMEM scratch that
persists across the sequentially-executed time windows of a member.  Every
slot step (departures -> enqueue -> BF-S refill -> alignment BF-J) runs
inside the kernel with no HBM round-trips; only the pre-generated
randomness streams are streamed in per window and only the per-slot
outputs (queue length, per-resource occupancy, departures) stream out.

The placement logic transcribes the bounded early-exit work list of
``repro.core.engine.bfjs_mr.run_bfjs_mr_streams`` with broadcasted-iota
masks and reductions in place of every dynamic index, and the resource
axis STATICALLY UNROLLED: vector state is stored as R stacked 2D planes
(demands ``(R, L, K)``, one ``(L, K)`` plane per resource, queue
demands ``(R, Qcap)``, and the per-server capacity input ``(R, L, 1)``),
so every per-resource feasibility comparison is a plain 2D vector op
against each server's own capacity.  The Tetris alignment score is exact
integer arithmetic compared as a normalized int32 ``(hi, lo)`` pair — the same
scheme as ``engine.ops.alignment_score_pair_jnp`` — so argmin tie-breaks
bit-match the scan engine (and, through it, the event-driven
``MultiResourceBFJS`` oracle) on every backend and lowering.  Trajectories
are bit-compatible with the scan engine whenever ``truncated`` stays 0 —
asserted by the interpret-mode parity + hypothesis suites in
tests/test_mr_kernel.py and tests/test_engine_parity_matrix.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import RES
from repro.kernels.common import (LANES, compiler_params, counter_spec,
                                  prefix_sum, resolve_windows, slot_out_shape,
                                  slot_spec, stream_block_bytes, tile_bytes,
                                  to_windows)

INF_SLOT = jnp.iinfo(jnp.int32).max
INT32_MAX = jnp.iinfo(jnp.int32).max


def bfjs_mr_vmem_bytes(L: int, K: int, Qcap: int, A_max: int, R: int,
                       TW: int) -> int:
    """VMEM the fused multi-resource BF-J/S kernel takes on the chip: the
    scratch state (demand (R,L,K), dep (L,K), occupancy (R,L,1), queue
    demand (R,Qcap), queue meta (2,Qcap)), the double-buffered capacity
    input (R,L,1) and (TW, A_max*R) size and (TW, A_max) duration blocks,
    and the compiler's spills — 12 (L,128) planes and three (A_max,Qcap)
    planes of the arrival-to-queue gathers.  All padded to (8,128) tiles.
    The spill counts are fitted (at R=2) to the v5e compiler's allocation
    and kept honest by tests/test_tpu_compile.py."""
    return ((R + 1) * tile_bytes(L, K) + 3 * R * tile_bytes(L, 1)
            + tile_bytes(R, Qcap) + tile_bytes(2, Qcap)
            + stream_block_bytes(TW, A_max * R, A_max)
            + 12 * tile_bytes(L, LANES) + 3 * tile_bytes(A_max, Qcap))


def _bfjs_mr_kernel(n_ref, sizes_ref, durs_ref, cap_ref,
                    qlen_ref, occ_out_ref, ndep_ref, dropped_ref, trunc_ref,
                    steps_ref, bfs_ref, fit_ref,
                    dem_ref, dep_ref, occ_ref, qdem_ref, qmeta_ref, acc_ref,
                    *, L, K, R, Qcap, A_max, W, TW, EARLY_EXIT):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        dem_ref[...] = jnp.zeros((R, L, K), jnp.int32)
        dep_ref[...] = jnp.full((L, K), INF_SLOT, jnp.int32)
        occ_ref[...] = jnp.zeros((R, L, 1), jnp.int32)
        qdem_ref[...] = jnp.zeros((R, Qcap), jnp.int32)
        row = jax.lax.broadcasted_iota(jnp.int32, (2, Qcap), 0)
        # row 0: qdur (init 1), row 1: qseq (init -1)
        qmeta_ref[...] = jnp.where(row == 0, 1, -1)
        for i in range(7):
            acc_ref[i] = 0

    l_col = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    k_row = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    q_row = jax.lax.broadcasted_iota(jnp.int32, (1, Qcap), 1)
    a_row = jax.lax.broadcasted_iota(jnp.int32, (1, A_max), 1)
    aa = jax.lax.broadcasted_iota(jnp.int32, (A_max, A_max), 0)
    aq = jax.lax.broadcasted_iota(jnp.int32, (A_max, Qcap), 1)
    CH = LANES if Qcap % LANES == 0 else Qcap

    def has_fit(elig, avail, n_ch):
        """(L, 1): eligible servers with room for some queued job, i.e.
        the row-any of the (L, Qcap) fit plane, built one CH-lane chunk at
        a time over the first ``n_ch`` chunks, which hold every queued job
        (the slot's live-prefix bound, below); counts them in
        ``fit_chunks``."""
        def chunk(c, acc):
            off = pl.multiple_of(c * CH, CH)
            fits = elig & (qmeta_ref[1:2, pl.ds(off, CH)] >= 0)
            for r in range(R):
                fits = fits & (qdem_ref[r:r + 1, pl.ds(off, CH)] <= avail[r])
            return acc | fits.any(axis=1, keepdims=True).astype(jnp.int32)
        acc_ref[6] = acc_ref[6] + n_ch
        return jax.lax.fori_loop(0, n_ch, chunk,
                                 jnp.zeros((L, 1), jnp.int32)) != 0

    def slot_step(tt, carry):
        q_cnt, seq0, dropped, trunc, steps, bfs = carry
        t = w * TW + tt

        # 1. departures free their demand vectors
        dep = dep_ref[...]
        leaving = dep == t                                   # (L, K)
        freed = leaving.any(axis=1, keepdims=True)           # (L, 1)
        n_dep = leaving.sum()
        for r in range(R):
            dem_r = dem_ref[r]
            occ_ref[r] = occ_ref[r] - jnp.sum(jnp.where(leaving, dem_r, 0),
                                              axis=1, keepdims=True)
            dem_ref[r] = jnp.where(leaving, 0, dem_r)
        dep_ref[...] = jnp.where(leaving, INF_SLOT, dep)

        # 2. arrivals -> first empty queue positions (sequential masked
        # insert: identical landing positions to the engine's
        # cumsum/searchsorted; arrival a gets seq id seq0 + a)
        n_t = n_ref[0, tt]
        qdem = qdem_ref[...]
        qmeta = qmeta_ref[...]
        qdur, qseq = qmeta[0:1], qmeta[1:2]                  # (1, Qcap)
        new_pos = jnp.full((1, A_max), -1, jnp.int32)
        for a in range(A_max):
            empty = qseq < 0
            first = jnp.min(jnp.where(empty, q_row, Qcap))
            valid = a < n_t
            land = valid & (first < Qcap)
            wm = land & (q_row == first)                     # (1, Qcap)
            qdem = jnp.concatenate(
                [jnp.where(wm, jnp.maximum(
                    jnp.round(sizes_ref[0, tt, a * R + r] * RES),
                    1.0).astype(jnp.int32), qdem[r:r + 1])
                 for r in range(R)], axis=0)
            qdur = jnp.where(wm, durs_ref[0, tt, a], qdur)
            qseq = jnp.where(wm, seq0 + a, qseq)
            new_pos = jnp.where(land & (a_row == a), first, new_pos)
            dropped = dropped + jnp.where(valid & ~land, 1, 0)
            q_cnt = q_cnt + jnp.where(land, 1, 0)
        seq0 = seq0 + n_t
        qdem_ref[...] = qdem
        qmeta_ref[...] = jnp.concatenate([qdur, qseq], axis=0)
        # live-prefix bound: arrivals land at the lowest empty places and
        # the work list below only empties places, so every job queued
        # from here to the slot's end lies below the live high-water mark
        # and the chunks past it cannot change a has_fit answer
        hw = jnp.max(jnp.where(qseq >= 0, q_row + 1, 0))
        n_ch = (hw + CH - 1) // CH
        landed = new_pos >= 0                                # (1, A_max)
        n_landed = landed.sum()
        # landed arrival indices, compacted ascending, + their positions
        rank = prefix_sum(landed) - 1
        comp = landed & (rank == aa)                         # (A, A)
        pos_list = jnp.max(jnp.where(comp, new_pos, -1),
                           axis=1)[None, :]                  # (1, A_max)

        # 3+4. BF-S then BF-J as one bounded placement work list: each step
        # does the BF-S placement for the lowest-index freed, unblocked
        # server that still has a fitting queued job (job = largest total
        # demand, earliest seq), else attempts the next landed arrival on
        # the min-alignment feasible server.
        def work(wcarry):
            step, a_ptr, blocked, q_cnt, trunc, bfs, done_in = wcarry
            # the (L, 1) mask rides the loop as int32: Mosaic cannot carry
            # bool vectors across loop iterations
            blocked = blocked != 0
            dep = dep_ref[...]
            qdem = qdem_ref[...]
            qmeta = qmeta_ref[...]
            qdur, qseq = qmeta[0:1], qmeta[1:2]
            avail = [cap_ref[r] - occ_ref[r] for r in range(R)]  # (L, 1)

            # BF-S candidate: the fit row of server `cur`
            cur = jnp.min(jnp.where(has_fit(freed & ~blocked, avail, n_ch),
                                    l_col, L))
            any_bfs = cur < L
            fit_cur = any_bfs & (qseq >= 0)                  # (1, Qcap)
            for r in range(R):
                avail_cur = jnp.sum(jnp.where(l_col == cur, avail[r], 0))
                fit_cur = fit_cur & (qdem[r:r + 1] <= avail_cur)
            tot = jnp.zeros((1, Qcap), jnp.int32)
            for r in range(R):
                tot = tot + qdem[r:r + 1]
            best_tot = jnp.max(jnp.where(fit_cur, tot, -1))
            cand = fit_cur & (tot == best_tot)
            best_seq = jnp.min(jnp.where(cand, qseq, INT32_MAX))
            j_bfs = jnp.min(jnp.where(cand & (qseq == best_seq), q_row,
                                      Qcap))
            j_bfs = jnp.minimum(j_bfs, Qcap - 1)

            # BF-J candidate: next landed arrival still in the queue, on
            # the min-alignment feasible server (any server, not just
            # freed — the oracle's _best_server scans all L).
            is_bfj = (~any_bfs) & (a_ptr < n_landed)
            ap = jnp.minimum(a_ptr, A_max - 1)
            pos = jnp.max(jnp.where(a_row == ap, pos_list, -1))
            posc = jnp.maximum(pos, 0)
            seq_pos = jnp.sum(jnp.where(q_row == posc, qseq, 0))
            present = is_bfj & (pos >= 0) & (seq_pos >= 0)
            d_bfj = [jnp.sum(jnp.where(q_row == posc, qdem[r:r + 1], 0))
                     for r in range(R)]
            feas = jnp.ones((L, 1), bool)
            for r in range(R):
                feas = feas & (d_bfj[r] <= avail[r])
            # exact alignment score as a normalized int32 (hi, lo) pair —
            # same scheme as engine.ops.alignment_score_pair_jnp, so the
            # lexicographic argmin equals the oracle's exact float64
            # argmin on every backend and lowering
            s_hi = avail[0] * (d_bfj[0] >> 8)
            s_lo = avail[0] * (d_bfj[0] & 255)
            for r in range(1, R):
                s_hi = s_hi + avail[r] * (d_bfj[r] >> 8)
                s_lo = s_lo + avail[r] * (d_bfj[r] & 255)
            s_hi = s_hi + (s_lo >> 8)
            s_lo = s_lo & 255
            best_hi = jnp.min(jnp.where(feas, s_hi, INT32_MAX))
            cand_j = feas & (s_hi == best_hi)
            best_lo = jnp.min(jnp.where(cand_j, s_lo, INT32_MAX))
            s_bfj = jnp.min(jnp.where(cand_j & (s_lo == best_lo), l_col,
                                      L))
            s_bfj = jnp.minimum(s_bfj, L - 1)
            ok_bfj = present & feas.any()

            do = any_bfs | ok_bfj
            tgt = jnp.where(any_bfs, jnp.minimum(cur, L - 1), s_bfj)
            qidx = jnp.where(any_bfs, j_bfs, posc)
            d_place = [jnp.sum(jnp.where(q_row == qidx, qdem[r:r + 1], 0))
                       for r in range(R)]
            dur = jnp.sum(jnp.where(q_row == qidx, qdur, 0))

            # first empty slot of the target server
            dep_row = jnp.sum(jnp.where(l_col == tgt, dep, 0),
                              axis=0, keepdims=True)         # (1, K)
            slot = jnp.min(jnp.where(dep_row == INF_SLOT, k_row, K))
            ok_slot = slot < K
            place = do & ok_slot
            wm = (l_col == tgt) & (k_row == jnp.minimum(slot, K - 1)) \
                & place                                      # (L, K)
            for r in range(R):
                dem_ref[r] = jnp.where(wm, d_place[r], dem_ref[r])
                occ_ref[r] = occ_ref[r] + jnp.where((l_col == tgt) & place,
                                                    d_place[r], 0)
            dep_ref[...] = jnp.where(wm, t + dur, dep)
            clr = (q_row == qidx) & place
            qdem_ref[...] = jnp.concatenate(
                [jnp.where(clr, 0, qdem[r:r + 1]) for r in range(R)],
                axis=0)
            qmeta_ref[...] = jnp.concatenate(
                [qdur, jnp.where(clr, -1, qseq)], axis=0)
            q_cnt = q_cnt - place.astype(jnp.int32)
            bfs = bfs + (place & any_bfs).astype(jnp.int32)
            # K-full server: the oracle would place; count, don't spin.
            trunc = trunc + (do & ~ok_slot).astype(jnp.int32)
            blocked = blocked | (any_bfs & ~ok_slot)
            a_ptr = a_ptr + is_bfj.astype(jnp.int32)
            # The scan engine's early-exit rule: with no BF-S fit left and
            # every landed arrival consumed, no later step can do work
            # (queues only shrink, avail only shrinks, freed&~blocked only
            # shrinks), so remaining steps are no-ops.
            done = (~any_bfs) & (a_ptr >= n_landed)
            # a step counts until one reports done (the no-op steps the
            # fixed-bound loop runs after it are not work)
            return (step + jnp.where(done_in, 0, 1), a_ptr,
                    blocked.astype(jnp.int32), q_cnt, trunc, bfs, done)

        winit = (jnp.int32(0), jnp.int32(0), jnp.zeros((L, 1), jnp.int32),
                 q_cnt, trunc, bfs, jnp.bool_(False))
        if EARLY_EXIT:
            # Same body, but stop as soon as a step reports done — the
            # scan engine exits here too, and post-done steps are no-ops,
            # so the trajectory is bit-identical by construction.
            n_steps, a_ptr, blocked, q_cnt, trunc, bfs, _ = \
                jax.lax.while_loop(
                    lambda c: (c[0] < W) & jnp.logical_not(c[-1]), work,
                    winit)
        else:
            n_steps, a_ptr, blocked, q_cnt, trunc, bfs, _ = \
                jax.lax.fori_loop(0, W, lambda _, c: work(c), winit)
        steps = steps + n_steps

        # saturation check (same rule as the scan engine): work the oracle
        # would still do => the bounded list diverged this slot.
        qdem = qdem_ref[...]
        qseq = qmeta_ref[...][1:2]
        avail = [cap_ref[r] - occ_ref[r] for r in range(R)]
        pend_bfs = has_fit(freed & (blocked == 0), avail, n_ch).any()
        left = (a_row >= a_ptr) & (a_row < n_landed)
        gmask = aq == jnp.maximum(pos_list, 0).T             # (A_max, Qcap)
        seq_at = jnp.sum(jnp.where(gmask, qseq, 0), axis=1)[None, :]
        present_l = left & (pos_list >= 0) & (seq_at >= 0)
        feas_l = jnp.ones((A_max, L), bool)
        for r in range(R):
            d_l = jnp.sum(jnp.where(gmask, qdem[r:r + 1], 0),
                          axis=1)[:, None]                   # (A_max, 1)
            feas_l = feas_l & (d_l <= avail[r].T)
        pend_bfj = (present_l & feas_l.any(axis=1)[None, :]).any()
        trunc = trunc + (pend_bfs | pend_bfj).astype(jnp.int32)

        qlen_ref[0, tt] = q_cnt
        for r in range(R):
            occ_out_ref[r, tt] = jnp.sum(occ_ref[r]).astype(jnp.float32) / RES
        ndep_ref[0, tt] = n_dep.astype(jnp.int32)
        return q_cnt, seq0, dropped, trunc, steps, bfs

    carry = jax.lax.fori_loop(
        0, TW, slot_step, tuple(acc_ref[i] for i in range(6)))
    for i, v in enumerate(carry):
        acc_ref[i] = v
    dropped_ref[0, 0], trunc_ref[0, 0] = carry[2], carry[3]
    steps_ref[0, 0], bfs_ref[0, 0] = carry[4], carry[5]
    fit_ref[0, 0] = acc_ref[6]


@functools.partial(
    jax.jit,
    static_argnames=("L", "K", "Qcap", "A_max", "work_steps", "window",
                     "interpret", "early_exit"))
def bfjs_mr_pallas(n: jax.Array, sizes: jax.Array, durs: jax.Array,
                   cap: jax.Array, L: int, K: int, Qcap: int, A_max: int,
                   work_steps: int, window: int | None = None,
                   interpret: bool = False, early_exit: bool = True):
    """Run the fused multi-resource BF-J/S slot engine on an ensemble.

    n (G, T) int32, sizes (G, T, A_max, R) f32, durs (G, T, D) int32 with
    the per-arrival durations in the last A_max lanes (D = A_max for
    streams_from_trace, D = L*K+A_max for make_streams) — one pre-generated
    stream set per ensemble member (only those lanes are streamed into
    the kernel).  ``cap`` is the (L, R) int32 per-server capacity plane on
    the ``quantize.RES`` grid (``core.engine.workload.capacity_plane``),
    held in VMEM as R (L, 1) planes.  Returns per-slot (queue_len (G, T),
    occupancy (G, T, R), departures (G, T)) plus (dropped, truncated,
    steps, bfs_placements, fit_chunks) of shape (G,).

    ``fit_chunks`` counts the CH-lane queue chunks (CH = 128 where Qcap
    allows it, else Qcap) the BF-S fit search scanned over its calls, one
    a work step and one a slot for the saturation check.  A call scans
    only the queue's live prefix, the chunks below the highest place
    queued after the slot's arrivals (exact: DESIGN.md §8), so
    ``fit_chunks / (steps + T)`` is about 1 while the queue holds under CH
    jobs and grows to Qcap / CH as it fills.

    ``window`` splits the horizon into VMEM-sized chunks: the grid is
    (G, T//window) and simulation state persists in scratch across a
    member's sequentially-executed windows.  Must divide T (default: whole
    horizon in one window).
    """
    G, T, A_sz, R = sizes.shape
    if A_sz != A_max:
        raise ValueError(f"sizes carry A_max={A_sz}, expected {A_max}")
    if cap.shape != (L, R):
        raise ValueError(
            f"capacity plane of shape {cap.shape}, expected (L, R) = "
            f"{(L, R)}")
    TW, NW = resolve_windows(T, window)
    D = durs.shape[-1]
    kernel = functools.partial(
        _bfjs_mr_kernel, L=L, K=K, R=R, Qcap=Qcap, A_max=A_max,
        W=work_steps, TW=TW, EARLY_EXIT=early_exit)
    qlen, occ, ndep, dropped, trunc, steps, bfs, fit = pl.pallas_call(
        kernel,
        grid=(G, NW),
        out_shape=(slot_out_shape(G, T, TW, jnp.int32),
                   jax.ShapeDtypeStruct((G, NW, R, TW), jnp.float32),
                   slot_out_shape(G, T, TW, jnp.int32))
                  + (jax.ShapeDtypeStruct((G, 1, 1), jnp.int32),) * 5,
        in_specs=[slot_spec(TW),
                  pl.BlockSpec((1, TW, A_max * R), lambda g, w: (g, w, 0)),
                  pl.BlockSpec((1, TW, A_max), lambda g, w: (g, w, 0)),
                  pl.BlockSpec((R, L, 1), lambda g, w: (0, 0, 0))],
        out_specs=(slot_spec(TW),
                   pl.BlockSpec((None, None, R, TW),
                                lambda g, w: (g, w, 0, 0),
                                memory_space=pltpu.SMEM),
                   slot_spec(TW)) + (counter_spec(),) * 5,
        scratch_shapes=[pltpu.VMEM((R, L, K), jnp.int32),
                        pltpu.VMEM((L, K), jnp.int32),
                        pltpu.VMEM((R, L, 1), jnp.int32),
                        pltpu.VMEM((R, Qcap), jnp.int32),
                        pltpu.VMEM((2, Qcap), jnp.int32),
                        pltpu.SMEM((7,), jnp.int32)],
        compiler_params=compiler_params(
            bfjs_mr_vmem_bytes(L, K, Qcap, A_max, R, TW)),
        interpret=interpret,
    )(to_windows(n, TW), sizes.reshape(G, T, A_max * R), durs[..., D - A_max:],
      cap.astype(jnp.int32).T[..., None])
    return (qlen.reshape(G, T), occ.transpose(0, 1, 3, 2).reshape(G, T, R),
            ndep.reshape(G, T), dropped[:, 0, 0], trunc[:, 0, 0],
            steps[:, 0, 0], bfs[:, 0, 0], fit[:, 0, 0])
