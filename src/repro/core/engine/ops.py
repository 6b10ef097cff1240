"""Primitive scheduling ops shared by the engines and the serving stack.

Pure-jnp, jit/vmap-friendly.  The Pallas kernels under ``repro.kernels``
re-express the hot ones with broadcasted-iota masks; these are the
behavioural definitions they are tested against.
"""
from __future__ import annotations



import jax
import jax.numpy as jnp

from ..partition import k_red
from ..quantize import RES


def best_fit_server(residuals: jax.Array, size: jax.Array) -> jax.Array:
    """Tightest feasible server for one job: argmin residual among residuals
    >= size; returns -1 if none fits. O(L) vectorized."""
    feasible = residuals >= size
    masked = jnp.where(feasible, residuals, jnp.inf)
    idx = jnp.argmin(masked)
    return jnp.where(feasible.any(), idx, -1)


def best_fit_place(residuals: jax.Array, sizes: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Sequentially Best-Fit place a batch of jobs (pure-jnp reference used by
    the serving engine; kernels/best_fit provides the Pallas TPU version).

    Returns (assignment (N,) int32 with -1 = rejected, new residuals)."""

    def body(resid, size):
        srv = best_fit_server(resid, size)
        ok = srv >= 0
        resid = jnp.where(ok, resid.at[srv].add(-size), resid)
        return resid, jnp.where(ok, srv, -1)

    new_resid, assign = jax.lax.scan(body, residuals, sizes)
    return assign.astype(jnp.int32), new_resid


def slot_sum(x: jax.Array) -> jax.Array:
    """Sum of float job sizes over the last (job-slot) axis, keepdims, in
    one fixed order: the slots zero-padded to a power of two, then halved
    pairwise (``x[:h] + x[h:]``) down to one.

    Float addition is not associative, and XLA and the TPU kernel compiler
    each pick their own tree for ``sum``, so on a TPU the scan engine and
    the fused kernel disagreed in the last bit — and a residual's last bit
    decides best-fit ties.  Every BF-J/S engine and the kernel sum in this
    one written-out order (pure slices and adds, which no compiler
    reassociates), so residuals agree bit for bit on every backend."""
    w = x.shape[-1]
    pad = (1 << (w - 1).bit_length()) - w
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x


def server_sum(col: jax.Array) -> jax.Array:
    """Sum of an ``(L, 1)`` per-server column to ``(1, 1)`` in one fixed
    order: zero-padded 8-row blocks added in block order, then the 8
    partial sums pairwise.  The cluster-total counterpart of
    :func:`slot_sum`, shared by the engines and the kernel."""
    pad = -col.shape[0] % 8
    if pad:
        col = jnp.concatenate([col, jnp.zeros((pad, 1), col.dtype)], axis=0)
    acc = col[0:8]
    for b in range(1, col.shape[0] // 8):
        acc = acc + col[8 * b:8 * b + 8]
    p = [acc[i:i + 1] for i in range(8)]
    return ((p[0] + p[1]) + (p[2] + p[3])) + ((p[4] + p[5]) + (p[6] + p[7]))


def alignment_score_pair_jnp(avail: jax.Array,
                             demand: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Tetris alignment <demand, avail> per server (paper §VIII), exact.

    ``avail`` is (L, R) grid-integer availability, ``demand`` is (R,) grid
    integers.  The true score ``sum_r avail_r * demand_r`` needs up to
    ~34 bits — too wide for int32 and for a float32 mantissa, and float32
    accumulation is NOT portable: XLA is free to contract ``mul+add`` into
    an FMA in one lowering but not another (observed to differ with vmap
    batch width on CPU), which flips argmin tie-breaks.  Instead the score
    is returned as a normalized int32 pair ``(hi, lo)`` with
    ``score == hi * 256 + lo`` and ``0 <= lo < 256``: products against the
    split demand ``(d >> 8, d & 255)`` stay below 2**24 each, so every op
    is exact integer arithmetic and comparing ``(hi, lo)``
    lexicographically compares the exact scores — identical to the numpy
    oracle's exact float64 ``core.multi_resource.alignment_scores`` on any
    backend, batch width or compiler version.  Exact while
    ``R * capacity`` stays under ~128 server-capacities (int32 headroom).
    """
    a = avail.astype(jnp.int32)
    d = demand.astype(jnp.int32)
    hi = a[:, 0] * (d[0] >> 8)
    lo = a[:, 0] * (d[0] & 255)
    for r in range(1, a.shape[1]):
        hi = hi + a[:, r] * (d[r] >> 8)
        lo = lo + a[:, r] * (d[r] & 255)
    return hi + (lo >> 8), lo & 255


def first_empty_positions(empty: jax.Array,
                          want: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Scatter targets for admitting a masked batch into a fixed buffer.

    ``empty`` is the buffer's ``(Q,)`` empty-slot mask, ``want`` a ``(N,)``
    mask of items asking for a slot.  Returns ``(pos, landed)``: the i-th
    wanting item (in index order) is assigned the i-th empty slot, ``landed``
    masks the items that actually got one (``pos < Q``; entries of
    non-wanting items are garbage and must stay masked).  This is the
    admission rule every engine uses — slot arrivals and fault-preemption
    requeues go through the same first-empty order, so the scan engines and
    the reference oracles agree on queue layout bit-for-bit.
    """
    n_empty = jnp.cumsum(empty.astype(jnp.int32))
    rank = jnp.cumsum(want.astype(jnp.int32)) - 1
    pos = jnp.searchsorted(n_empty, rank + 1)
    return pos, want & (pos < empty.shape[0])


def largest_fitting_job(queue: jax.Array, cap: jax.Array) -> jax.Array:
    """Index of the largest queued job with size <= cap (BF-S step);
    -1 if none. Zero entries mean empty queue slots."""
    fits = (queue > 0) & (queue <= cap)
    masked = jnp.where(fits, queue, -jnp.inf)
    idx = jnp.argmax(masked)
    return jnp.where(fits.any(), idx, -1)


def k_red_jnp(J: int) -> jax.Array:
    """The reduced configuration set K_RED^(J) as an int32 array (a constant
    when used under jit; ``k_red`` itself is lru-cached host-side)."""
    return jnp.asarray(k_red(J), jnp.int32)


def max_weight_config_jax(J: int, vq_sizes: jax.Array) -> tuple[jax.Array, jax.Array]:
    """argmax_{k in K_RED^{(J)}} <k, Q>  (paper Eq. 8), jit/vmap-friendly."""
    confs = k_red_jnp(J)
    w = confs @ vq_sizes.astype(jnp.int32)
    i = jnp.argmax(w)
    return i, confs[i]


def vq_type_of_grid(g: jax.Array, J: int) -> jax.Array:
    """Partition-I type of integer grid sizes (exact, jittable).

    Transcribes ``PartitionI.type_of`` comparison-for-comparison:
    ``m = #{k in 1..J : g <= RES >> k}`` clipped to ``J-1``, even/odd split
    by ``3g > 2*(RES >> m)``, and the ``g <= RES >> J`` tail mapping to the
    last type ``2J - 1``.  Agrees with ``PartitionI.type_of_scalar`` on
    every grid point — the VQS engines classify with this so their virtual
    queues are bit-identical to the event-driven engine's.
    """
    g = jnp.asarray(g, jnp.int32)
    bounds = jnp.asarray([RES >> k for k in range(1, J + 1)], jnp.int32)
    m = jnp.minimum((g[..., None] <= bounds).sum(-1).astype(jnp.int32), J - 1)
    upper = jnp.right_shift(jnp.int32(RES), m)
    t = jnp.where(3 * g > 2 * upper, 2 * m, 2 * m + 1)
    return jnp.where(g <= (RES >> J), 2 * J - 1, t).astype(jnp.int32)


def vq_type_of(sizes: jax.Array, J: int) -> jax.Array:
    """Partition-I type of float sizes in (0,1] (vectorized, jittable).

    Sizes are quantized to the ``quantize.RES`` grid (the same
    ``max(round(size * RES), 1)`` rule the engines apply) and classified by
    the exact integer rule, so the result agrees with
    ``PartitionI.type_of_scalar`` on every grid point (including exact
    powers of two and the ``size <= 2^-J`` tail).
    """
    g = jnp.maximum(jnp.round(sizes * RES), 1.0).astype(jnp.int32)
    return vq_type_of_grid(g, J)
