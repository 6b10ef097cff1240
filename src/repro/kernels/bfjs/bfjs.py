"""Pallas TPU kernel: fused BF-J/S slot-step engine (DESIGN.md §4).

One program instance simulates one independent cluster of the Monte-Carlo
ensemble: the grid is ``(G, NW)`` — ensemble member x time window — and the
whole mutable simulation state (per-slot job sizes, departure slots, the
queue buffer and the running counters) lives in VMEM scratch that persists
across the sequentially-executed time windows of a member.  Every slot step
(departures -> enqueue -> BF-S refill -> BF-J placement) runs inside the
kernel with no HBM round-trips; only the pre-generated randomness streams
(arrival counts, job sizes, service durations) are streamed in per window
and only the per-slot outputs (queue length, occupancy, departures) are
streamed out.

The placement logic is a transcription of the bounded masked-select work
list of ``repro.core.jax_sched.run_bfjs_streams`` (see DESIGN.md §2): no
``cond``, no data-dependent trip counts, every dynamic index expressed as a
broadcasted-iota mask + reduction so the body is pure vector ops.
Trajectories are bit-compatible with the pure-JAX engine (and therefore
with the reference engine) whenever the ``truncated`` counter stays 0 —
asserted by the interpret-mode parity tests in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.engine.ops import server_sum, slot_sum
from repro.kernels.common import (LANES, compiler_params, counter_spec,
                                  prefix_sum, resolve_windows, slot_out_shape,
                                  slot_spec, stream_block_bytes, tile_bytes,
                                  to_windows)

INF_SLOT = jnp.iinfo(jnp.int32).max
BIG = 3.4e38  # ~f32 max; infeasibility sentinel (matches kernels/best_fit)


def bfjs_vmem_bytes(L: int, K: int, Qcap: int, A_max: int, TW: int) -> int:
    """VMEM the fused BF-J/S kernel takes on the chip: the scratch state
    (srv and dep (L,K), queue (1,Qcap)), the double-buffered (TW, A_max)
    size and (TW, L*K+A_max) duration blocks, and the compiler's spills —
    six (L,128) planes (per-server residual and masks) and two (Qcap,128)
    planes of the saturation check's transposed queue gather.  All padded
    to (8,128) tiles.  The spill counts are fitted to the v5e compiler's
    allocation and kept honest by tests/test_tpu_compile.py."""
    return (2 * tile_bytes(L, K) + tile_bytes(1, Qcap)
            + stream_block_bytes(TW, A_max, L * K + A_max)
            + 6 * tile_bytes(L, LANES) + 2 * tile_bytes(Qcap, LANES))


def _bfjs_kernel(n_ref, sizes_ref, durs_ref,
                 qlen_ref, occ_ref, ndep_ref, dropped_ref, trunc_ref,
                 srv_ref, dep_ref, queue_ref, acc_ref,
                 *, L, K, Qcap, A_max, W, TW):
    w = pl.program_id(1)
    D = L * K + A_max

    @pl.when(w == 0)
    def _init():
        srv_ref[...] = jnp.zeros((L, K), jnp.float32)
        dep_ref[...] = jnp.full((L, K), INF_SLOT, jnp.int32)
        queue_ref[...] = jnp.zeros((1, Qcap), jnp.float32)
        for i in range(3):
            acc_ref[i] = 0

    l_iota = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (L, K), 1)
    q_iota = jax.lax.broadcasted_iota(jnp.int32, (1, Qcap), 1)
    a_iota = jax.lax.broadcasted_iota(jnp.int32, (1, A_max), 1)
    d_iota = jax.lax.broadcasted_iota(jnp.int32, (1, D), 1)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (A_max, A_max), 0)

    def slot_step(tt, carry):
        q_cnt, dropped, trunc = carry
        t = w * TW + tt

        # 1. departures
        dep = dep_ref[...]
        srv = srv_ref[...]
        leaving = dep == t
        freed = leaving.any(axis=1, keepdims=True)          # (L, 1)
        n_dep = leaving.sum()
        srv = jnp.where(leaving, 0.0, srv)
        srv_ref[...] = srv
        dep_ref[...] = jnp.where(leaving, INF_SLOT, dep)

        # 2. arrivals -> first empty queue slots (sequential masked insert:
        # identical landing positions to the engine's cumsum/searchsorted)
        n_t = n_ref[0, tt]
        queue = queue_ref[...]
        new_pos = jnp.full((1, A_max), -1, jnp.int32)
        for a in range(A_max):
            empty = queue == 0.0
            first = jnp.min(jnp.where(empty, q_iota, Qcap))
            valid = a < n_t
            land = valid & (first < Qcap)
            size_a = sizes_ref[0, tt, a]
            queue = jnp.where(land & (q_iota == first), size_a, queue)
            new_pos = jnp.where(land & (a_iota == a), first, new_pos)
            dropped = dropped + jnp.where(valid & ~land, 1, 0)
            q_cnt = q_cnt + jnp.where(land, 1, 0)
        queue_ref[...] = queue
        landed = new_pos >= 0                                # (1, A_max)
        n_landed = landed.sum()
        # landed arrival indices, compacted ascending, + their positions
        rank = prefix_sum(landed) - 1
        comp = landed & (rank == r_iota)                     # (A, A)
        landed_list = jnp.min(jnp.where(comp, a_iota, A_max - 1),
                              axis=1)[None, :]               # (1, A_max)
        pos_list = jnp.max(jnp.where(comp, new_pos, -1), axis=1)[None, :]

        durs_t = durs_ref[0, pl.ds(tt, 1), :]                # (1, D)

        # 3+4. BF-S then BF-J as one bounded placement work list: each step
        # does the BF-S placement for the lowest-index freed server that
        # still has a fitting job, else attempts the next landed arrival.
        def work(_, wcarry):
            dc, a_ptr, n_placed = wcarry
            srv = srv_ref[...]
            queue = queue_ref[...]
            resid = 1.0 - slot_sum(srv)                        # (L, 1)
            occupied = queue > 0.0
            qmin = jnp.min(jnp.where(occupied, queue, BIG))
            fits = freed & (resid >= qmin) & (qmin < BIG)
            cur = jnp.min(jnp.where(fits, l_iota, L))
            any_bfs = cur < L

            # BF-S candidate: largest fitting job for server `cur`
            resid_cur = jnp.max(jnp.where(l_iota == cur, resid, -BIG))
            fitq = jnp.where(occupied & (queue <= resid_cur), queue, -BIG)
            size_bfs = jnp.max(fitq)
            j_bfs = jnp.min(jnp.where((fitq == size_bfs) & occupied,
                                      q_iota, Qcap))

            # BF-J candidate: next landed arrival, one attempt each
            is_bfj = (~any_bfs) & (a_ptr < n_landed)
            ap = jnp.minimum(a_ptr, A_max - 1)
            a = jnp.max(jnp.where(a_iota == ap, landed_list, -1))
            pos = jnp.max(jnp.where(a_iota == ap, pos_list, -1))
            size_bfj = jnp.max(jnp.where(q_iota == pos, queue, -BIG))
            size_bfj = jnp.where(pos >= 0, size_bfj, 0.0)
            feasible = (resid >= size_bfj) & (size_bfj > 0)
            best_r = jnp.min(jnp.where(feasible, resid, BIG))
            s_bfj = jnp.min(jnp.where(feasible & (resid == best_r),
                                      l_iota, L))
            ok_bfj = is_bfj & (s_bfj < L)

            do = any_bfs | ok_bfj
            tgt = jnp.where(any_bfs, cur, s_bfj)
            qidx = jnp.where(do, jnp.where(any_bfs, j_bfs,
                                           jnp.maximum(pos, 0)), Qcap)
            size = jnp.where(any_bfs, size_bfs, size_bfj)
            didx = jnp.where(any_bfs, jnp.minimum(dc, D - 1),
                             jnp.minimum(L * K + a, D - 1))
            dur = jnp.max(jnp.where(d_iota == didx, durs_t, -1))

            # first empty slot of the target server (slot 0 when full,
            # replicating the reference engine's argmax-of-all-False)
            row_m = l_iota == tgt
            slot = jnp.min(jnp.where(row_m & (srv == 0.0), k_iota, K))
            slot = jnp.where(slot == K, 0, slot)
            wmask = row_m & (k_iota == slot) & do
            srv_ref[...] = jnp.where(wmask, size, srv)
            dep_ref[...] = jnp.where(wmask, t + dur, dep_ref[...])
            queue_ref[...] = jnp.where(q_iota == qidx, 0.0, queue)
            return (dc + any_bfs.astype(jnp.int32),
                    a_ptr + is_bfj.astype(jnp.int32),
                    n_placed + do.astype(jnp.int32))

        _, a_ptr, n_placed = jax.lax.fori_loop(
            0, W, work, (jnp.int32(0), jnp.int32(0), jnp.int32(0)))
        q_cnt = q_cnt - n_placed

        # saturation check (same rule as the pure-JAX engine): a placement
        # the reference engine would still make => divergence this slot.
        srv = srv_ref[...]
        queue = queue_ref[...]
        resid = 1.0 - slot_sum(srv)
        qmin = jnp.min(jnp.where(queue > 0.0, queue, BIG))
        pend_bfs = (freed & (resid >= qmin) & (qmin < BIG)).any()
        left = (a_iota >= a_ptr) & (a_iota < n_landed)
        sz_left = jnp.max(
            jnp.where(q_iota.T == pos_list, queue.T, -BIG), axis=0,
            keepdims=True)                                    # (1, A_max)
        pend_bfj = (left & (sz_left > 0)
                    & (sz_left <= jnp.max(resid))).any()
        trunc = trunc + (pend_bfs | pend_bfj).astype(jnp.int32)

        qlen_ref[0, tt] = q_cnt
        occ_ref[0, tt] = server_sum(slot_sum(srv))[0, 0]
        ndep_ref[0, tt] = n_dep.astype(jnp.int32)
        return q_cnt, dropped, trunc

    carry = jax.lax.fori_loop(
        0, TW, slot_step, (acc_ref[0], acc_ref[1], acc_ref[2]))
    for i, v in enumerate(carry):
        acc_ref[i] = v
    q_cnt, dropped, trunc = carry
    dropped_ref[0, 0] = dropped
    trunc_ref[0, 0] = trunc


@functools.partial(
    jax.jit,
    static_argnames=("L", "K", "Qcap", "A_max", "work_steps", "window",
                     "interpret"))
def bfjs_pallas(n: jax.Array, sizes: jax.Array, durs: jax.Array,
                L: int, K: int, Qcap: int, A_max: int,
                work_steps: int, window: int | None = None,
                interpret: bool = False):
    """Run the fused BF-J/S slot engine on an ensemble of clusters.

    n (G, T) int32, sizes (G, T, A_max) f32, durs (G, T, L*K+A_max) int32 —
    one pre-generated stream set per ensemble member (jax_sched.make_streams
    vmapped over keys).  Returns per-slot (queue_len, occupancy, departures)
    of shape (G, T) plus (dropped, truncated) of shape (G,).

    ``window`` splits the horizon into VMEM-sized chunks: the grid is
    (G, T//window) and simulation state persists in scratch across a
    member's sequentially-executed windows.  Must divide T (default: whole
    horizon in one window).
    """
    G, T = n.shape
    TW, NW = resolve_windows(T, window)
    D = L * K + A_max
    kernel = functools.partial(
        _bfjs_kernel, L=L, K=K, Qcap=Qcap, A_max=A_max, W=work_steps, TW=TW)
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
                  pl.BlockSpec((1, TW, D), lambda g, w: (g, w, 0))],
        out_specs=(slot_spec(TW), slot_spec(TW), slot_spec(TW),
                   counter_spec(), counter_spec()),
        scratch_shapes=[pltpu.VMEM((L, K), jnp.float32),
                        pltpu.VMEM((L, K), jnp.int32),
                        pltpu.VMEM((1, Qcap), jnp.float32),
                        pltpu.SMEM((3,), jnp.int32)],
        compiler_params=compiler_params(
            bfjs_vmem_bytes(L, K, Qcap, A_max, TW)),
        interpret=interpret,
    )(to_windows(n, TW), sizes, durs)
    return (qlen.reshape(G, T), occ.reshape(G, T), ndep.reshape(G, T),
            dropped[:, 0, 0], trunc[:, 0, 0])
