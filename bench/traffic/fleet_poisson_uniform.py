"""Two-resource traffic on a heterogeneous fleet: Poisson arrivals, (cpu,
mem) demands each uniform on ``sizes["demand_range"]`` and independent,
geometric service of mean ``1 / sizes["mu"]`` slots, on the machines of
the configuration's ``fleet``.

The interface of ``poisson_uniform`` (``sampler``, ``call_keys``,
``streams``) plus ``capacities(sizes)``, the ``(L, 2)`` capacity
plane.  The streams are ``poisson_uniform``'s copy of the program's
stream generator with an ``(n, 2)`` sampler: the key chain is the same, so
the program's two-resource draws (``(T, A_max, 2)`` demands) come out of
the same keys.  ``lam`` here is the arrival rate of the whole cluster, in
jobs a slot.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

from bench.traffic import poisson_uniform as pu

call_keys = pu.call_keys


def sampler(sizes: dict):
    """``sampler(key, n)``: ``(n, 2)`` demands, each uniform on
    ``demand_range``, float32."""
    return _pair_sampler(*map(float, sizes["demand_range"]))


@functools.lru_cache(maxsize=None)
def _pair_sampler(lo: float, hi: float):
    return functools.partial(_uniform_pair, lo=lo, hi=hi)


def _uniform_pair(key, n, *, lo, hi):
    return jax.random.uniform(key, (n, 2), minval=lo, maxval=hi)


def streams(key, sizes: dict, horizon: int):
    """One cluster's streams from ``key``, as the program draws them."""
    return pu.make_streams(key, lam=sizes["lam"], mu=sizes["mu"],
                           sampler=sampler(sizes), L=sizes["L"],
                           K=sizes["K"], A_max=sizes["A_max"],
                           horizon=horizon)


def capacities(sizes: dict) -> np.ndarray:
    """The ``(L, 2)`` (cpu, mem) capacity plane: ``sizes["machines"][i]``
    machines of class ``sizes["classes"][i]`` (its cpu and mem), in an
    order drawn from ``sizes["fleet_seed"]`` so that a server's index says
    nothing of its class."""
    rows = np.repeat(np.asarray(sizes["classes"], np.float64),
                     sizes["machines"], axis=0)
    if len(rows) != sizes["L"]:
        raise ValueError(f"the fleet has {len(rows)} machines for "
                         f"L={sizes['L']}")
    return rows[np.random.default_rng(sizes["fleet_seed"])
                .permutation(len(rows))]
