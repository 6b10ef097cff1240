"""First-class workload specification for the engine entry points.

The paper's simulation inputs — Poisson arrival rate, job-size sampler,
geometric service rate — used to travel through ``run_policy`` as loose
positional arguments, which baked the single-resource assumption into the
API: a sampler returned ``(n,)`` scalars and nothing carried the resource
count or per-resource server capacity.  ``Workload`` makes the workload the
typed object every entry point dispatches on:

    wl = Workload(lam=1.5, mu=0.01, sampler=sampler)          # R = 1
    wl = Workload(lam=1.5, mu=0.01, sampler=vec_sampler,
                  num_resources=2, capacity=(1.0, 1.0))       # (cpu, mem)
    wl = Workload(lam=1.5, mu=0.01, sampler=vec_sampler,
                  num_resources=2, capacity=caps)             # (L, 2) plane
    run_policy(wl, policy="bfjs", engine="scan", key=key, L=8, ...)

``sampler(key, n)`` must return ``(n,)`` float sizes in (0, 1] when
``num_resources == 1`` and ``(n, R)`` demand vectors in (0, 1]^R otherwise
— checked shape-only (``jax.eval_shape``, no FLOPs) by ``check_sampler``,
which every entry point calls before generating streams.  ``capacity`` is
the server capacity: one length-R tuple shared by every server, or an
``(L, R)`` plane with one row per server.  The single-resource engines
(``bfjs``, ``vqs``, ``vqs-bf``) support unit capacity only and reject
anything else loudly (``require_scalar``), while ``bfjs-mr`` honours
arbitrary per-server, per-resource capacities: its entry points broadcast
a tuple to the ``(L, R)`` plane and quantize it to the ``quantize.RES``
grid once (``capacity_plane``), so its oracle, scan engine and kernel
see only planes.

The PR 2 loose-argument signatures remain as deprecation shims in
``engine.api`` that build a ``Workload`` internally — bit-match regression
tested, so migrating callers is a pure refactor.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Callable

import jax
import numpy as np

from ..quantize import RES


@dataclass(frozen=True)
class Workload:
    """One cluster workload: arrivals, sizes, service, resource geometry.

    Attributes:
      lam: Poisson arrival rate (jobs per slot).
      mu: geometric service rate (mean service time ``1/mu`` slots).
      sampler: ``sampler(key, n) -> (n,)`` sizes (``R == 1``) or ``(n, R)``
        demand vectors (``R > 1``), values in (0, 1] per resource.
      num_resources: R, the length of every job's requirement vector.
      capacity: server capacity — a scalar (broadcast to all R
        resources), a length-R tuple (the same on every server) or an
        ``(L, R)`` per-server plane.  Normalized by ``normalize_capacity``
        to a tuple of floats, or a tuple of L such tuples for a plane
        (hashable, so a workload stays a valid static argument).
    """

    lam: float
    mu: float
    sampler: Callable[[jax.Array, int], jax.Array]
    num_resources: int = 1
    capacity: float | tuple = 1.0

    def __post_init__(self):
        if not isinstance(self.num_resources, int) or self.num_resources < 1:
            raise ValueError(
                f"num_resources must be a positive int, got "
                f"{self.num_resources!r}")
        if self.lam < 0:
            raise ValueError(f"lam must be >= 0, got {self.lam}")
        if not 0 < self.mu <= 1:
            raise ValueError(f"mu must be in (0, 1], got {self.mu}")
        object.__setattr__(self, "capacity", normalize_capacity(
            self.capacity, self.num_resources))

    # -- validation ---------------------------------------------------------
    def check_sampler(self) -> None:
        """Shape-check ``sampler`` against ``num_resources`` (no FLOPs).

        ``jax.eval_shape`` traces one abstract call ``sampler(key, 2)`` and
        verifies the output is ``(2,)`` for R == 1 / ``(2, R)`` for R > 1 —
        the mismatch every multi-resource bug starts with, caught at the
        API boundary instead of deep inside a scan."""
        key = jax.ShapeDtypeStruct((2,), jax.numpy.uint32)
        try:
            out = jax.eval_shape(lambda k: self.sampler(k, 2), key)
        except TypeError:
            # typed-key samplers (jax >= 0.4.16 PRNGKeyArray)
            key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
            out = jax.eval_shape(lambda k: self.sampler(k, 2), key)
        expect = (2,) if self.num_resources == 1 else (2, self.num_resources)
        if tuple(out.shape) != expect:
            raise ValueError(
                f"sampler output shape {tuple(out.shape)} does not match "
                f"num_resources={self.num_resources}: expected {expect} "
                "for sampler(key, 2)")

    def require_scalar(self, policy: str) -> None:
        """Single-resource engines reject vector workloads loudly."""
        if self.num_resources != 1:
            raise ValueError(
                f"policy {policy!r} is single-resource; this workload has "
                f"num_resources={self.num_resources} — use policy="
                "\"bfjs-mr\" (or collapse the demands first)")
        if self.capacity != (1.0,):
            cap = self.capacity
            got = (f"an ({len(cap)}, {len(cap[0])}) per-server capacity "
                   "plane" if isinstance(cap[0], tuple)
                   else f"capacity={cap}")
            raise ValueError(
                f"policy {policy!r} supports unit server capacity only, "
                f"got {got}; per-resource and per-server capacities run "
                "under policy=\"bfjs-mr\"")

    # -- ergonomics ---------------------------------------------------------
    def replace(self, **changes) -> "Workload":
        return dataclasses.replace(self, **changes)

    @property
    def mean_service(self) -> float:
        return 1.0 / self.mu


def normalize_capacity(capacity, R: int) -> tuple:
    """A capacity in its hashable normal form: a scalar or a length-R
    sequence becomes a length-R tuple of floats (the same capacity on
    every server), an ``(L, R)`` array-like a tuple of L such tuples (one
    row per server).  Every entry must be positive."""
    arr = np.asarray(capacity, dtype=np.float64)
    if arr.ndim == 0:
        arr = np.full(R, float(arr))
    if arr.ndim not in (1, 2) or arr.shape[-1] != R or arr.size == 0:
        raise ValueError(
            f"capacity of shape {arr.shape} for num_resources={R}: expected "
            f"a scalar, a length-{R} tuple or an (L, {R}) per-server plane")
    if not (arr > 0).all():
        raise ValueError(f"capacity entries must be > 0, got {capacity}")
    if arr.ndim == 1:
        return tuple(float(c) for c in arr)
    return tuple(tuple(float(c) for c in row) for row in arr)


def capacity_plane(capacity, L: int, R: int) -> np.ndarray:
    """The ``(L, R)`` int32 per-server capacity plane on the
    ``quantize.RES`` grid (``round(c * RES)``), the one form every
    ``bfjs-mr`` engine computes with.  A length-R capacity is broadcast to
    every server; a plane must have one row per server."""
    cap = np.asarray(normalize_capacity(capacity, R), dtype=np.float64)
    if cap.ndim == 2 and cap.shape[0] != L:
        raise ValueError(f"capacity plane has {cap.shape[0]} rows for "
                         f"L={L} servers")
    return np.rint(np.broadcast_to(cap, (L, R)) * RES).astype(np.int32)
