"""Synthetic traffic of the paper's Fig. 4 model (arXiv:1901.05998 Section
VII): Poisson arrivals, job sizes uniform on ``sizes["size_range"]``,
geometric service of mean ``1 / sizes["mu"]`` slots.

A traffic generator module gives, from a configuration's ``sizes``:

  * ``sampler(sizes)``: the job-size sampler handed to the program's
    ``Workload`` (the sweep path's program draws its streams on the
    device);
  * ``call_keys(seed, call, G)``: the ensemble keys of sweep call ``call``;
  * ``streams(key, sizes, horizon)``: one cluster's streams as the program
    draws them from ``key``, for the reference;
  * ``trace(sizes, params, seed)``: a host trace for the replay path.

``make_streams`` is a copy of the program's stream generator, kept here so
that the reference draws the same streams from the same keys without
importing the program.  Per slot, the key chain is ``key, _, k_n, k_sizes,
k_dur = split(key, 5)``; the arrival count is Poisson(lam) clipped to
``A_max``, the sizes are ``sampler(k_sizes, A_max)`` and the ``L*K +
A_max`` durations are geometric(mu), at least one slot.  ``lam`` and ``mu``
are compile-time constants, and the geometric scale ``1 / log1p(-mu)`` is
rounded to float32 once on the host and multiplied in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative integer seed (64 bits and more fold
    in, where ``PRNGKey`` alone would wrap them)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    high = seed >> 32
    while high:
        key = jax.random.fold_in(key, high & 0xFFFFFFFF)
        high >>= 32
    return key


def call_keys(seed: int, call: int, G: int) -> jax.Array:
    """The ensemble keys of sweep call ``call``: ``G`` keys split from
    ``fold_in(seed_key(seed), call)``."""
    return jax.random.split(jax.random.fold_in(seed_key(seed), call), G)


def sampler(sizes: dict):
    """``sampler(key, n)``: n sizes uniform on ``size_range``, float32."""
    return _uniform_sampler(*map(float, sizes["size_range"]))


@functools.lru_cache(maxsize=None)
def _uniform_sampler(lo: float, hi: float):
    return functools.partial(_uniform, lo=lo, hi=hi)


def _uniform(key, n, *, lo, hi):
    return jax.random.uniform(key, (n,), minval=lo, maxval=hi)


def _geometric(key, mu: float, shape) -> jax.Array:
    u = jax.random.uniform(key, shape, minval=1e-7, maxval=1.0)
    scale = np.float32(1.0 / math.log1p(-mu))
    return jnp.maximum(jnp.ceil(jnp.log(u) * scale), 1.0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("lam", "mu", "sampler", "L",
                                             "K", "A_max", "horizon"))
def make_streams(key, lam: float, mu: float, sampler, L: int, K: int,
                 A_max: int, horizon: int):
    """``(n, sizes, durs)`` of one cluster: shapes ``(T,)``, ``(T, A_max)``
    and ``(T, L*K + A_max)``."""
    def chain(k, _):
        ks = jax.random.split(k, 5)
        return ks[0], ks[1:]

    _, ks = jax.lax.scan(chain, key, None, length=horizon)
    n = jnp.minimum(jax.vmap(lambda k: jax.random.poisson(k, lam))(ks[:, 1]),
                    A_max).astype(jnp.int32)
    sizes = jax.vmap(lambda k: sampler(k, A_max))(ks[:, 2])
    durs = jax.vmap(lambda k: _geometric(k, mu, (L * K + A_max,)))(ks[:, 3])
    return n, sizes, durs


def streams(key, sizes: dict, horizon: int):
    """One cluster's streams from ``key``, as the program draws them."""
    return make_streams(key, lam=sizes["lam_per_server"] * sizes["L"],
                        mu=sizes["mu"], sampler=sampler(sizes), L=sizes["L"],
                        K=sizes["K"], A_max=sizes["A_max"], horizon=horizon)


def trace(sizes: dict, params: dict, seed: int) -> dict[str, np.ndarray]:
    """``{"arrival_slots", "size", "durations"}`` of ``params["trace_slots"]``
    slots, sorted by slot: Poisson(lam) arrivals a slot clipped to
    ``A_max``, float32 sizes uniform on ``size_range`` (so that every
    reader sees the same value), geometric(mu) durations of at least one
    slot.  The same seed gives the same trace."""
    rng = np.random.default_rng([seed, 0x7A11])
    T = params["trace_slots"]
    lam = sizes["lam_per_server"] * sizes["L"]
    counts = np.minimum(rng.poisson(lam, T), sizes["A_max"])
    n = int(counts.sum())
    lo, hi = sizes["size_range"]
    size = rng.uniform(lo, hi, n).astype(np.float32).astype(np.float64)
    return {"arrival_slots": np.repeat(np.arange(T, dtype=np.int64), counts),
            "size": size,
            "durations": rng.geometric(sizes["mu"], n).astype(np.int64)}
