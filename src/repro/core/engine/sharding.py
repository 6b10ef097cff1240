"""Mesh-sharded Monte-Carlo: the ensemble dimension G across devices.

The paper's headline evidence (Figs. 6/7) averages throughput over large
ensembles of independently-simulated clusters — embarrassingly parallel in
the ensemble dimension G.  This module places G on a 1-D
``jax.sharding.Mesh`` (axis ``"ensemble"``) via ``shard_map``: every
device traces the SAME per-policy Monte-Carlo program on its G/D shard of
the PRNG keys, so per-member randomness, the scan carries and the Pallas
kernel grid all stay device-local — no collectives anywhere, time windows
never cross devices (DESIGN.md §11).

Layout invariant the wrapper relies on: every ``PolicyResult`` field of a
Monte-Carlo run carries a LEADING G axis (``queue_len (G, T)``,
``occupancy (G, T[, R])``, ``departed (G, T)``, scalar counters ``(G,)``),
so one ``PartitionSpec("ensemble")`` prefix shards the whole pytree.
Because each member's simulation consumes exactly its own key — the same
key chain as the unsharded path — sharded results are BIT-IDENTICAL to
single-device runs, just laid out across devices
(tests/test_sharded_mc.py).

Engine rules:

  * ``"scan"`` / ``"pallas"`` run under ``shard_map``; the Pallas VMEM
    precheck sees the per-device local G, so footprints that overflow one
    device can still dispatch on a mesh (``kernels.common.pallas_precheck``);
  * ``"reference"`` is a host-side numpy oracle — not traceable, so
    ``mesh=`` is accepted but ignored (the run is host-serial either way;
    parity against it is what the sharded engines are tested for).

``monte_carlo_chunked`` composes the mesh with ``core.engine.chunked``:
per-chunk carries keep the full ``(G, ...)`` shape on the host checkpoint
(the manifest never pins a device count), so a sweep checkpointed on D
devices resumes bit-exactly on D' — re-sharding is just the next launch's
input placement.

On hosts without real accelerators, force a multi-device platform with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE importing
jax — how CI runs the 4-device smoke job.
"""
from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .streams import make_streams

#: The mesh axis the ensemble dimension is sharded over.
ENSEMBLE_AXIS = "ensemble"


def resolve_mesh(mesh: Mesh | None = None,
                 devices: int | list | None = None) -> Mesh | None:
    """Normalize the ``mesh=``/``devices=`` knobs to a 1-D Mesh (or None).

    ``devices=`` is the convenience form: an int takes the first N of
    ``jax.devices()``, a sequence of Device objects is used as given —
    either way on a fresh 1-D mesh with axis :data:`ENSEMBLE_AXIS`.  A
    ready-made ``mesh=`` must be 1-D (the ensemble is the only sharded
    dimension; time windows stay per-device).  Both None means unsharded.
    """
    if mesh is not None and devices is not None:
        raise ValueError("pass mesh= or devices=, not both")
    if mesh is not None:
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"ensemble sharding needs a 1-D mesh; got axes "
                f"{mesh.axis_names} (only the ensemble dimension G is "
                "sharded — time windows stay per-device)")
        return mesh
    if devices is None:
        return None
    if isinstance(devices, int):
        avail = jax.devices()
        if devices > len(avail):
            raise ValueError(
                f"devices={devices} but only {len(avail)} JAX device(s) "
                "are visible; on CPU hosts set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N before importing "
                "jax")
        devices = avail[:devices]
    return Mesh(np.asarray(devices), (ENSEMBLE_AXIS,))


def ensemble_streams(workload, keys, *, L: int = 8, K: int = 16,
                     A_max: int = 8, horizon: int = 10_000,
                     fault_rate: float = 0.0, repair_rate: float = 1.0):
    """(G, ...)-batched ``SchedStreams``, one member per key.

    ``jax.vmap(make_streams)`` preserves the exact per-key chain, so member
    g's planes are bit-identical to ``make_streams(keys[g], ...)`` — the
    invariant that makes chunked/sharded Monte-Carlo interchangeable with
    the per-key engines."""
    workload.check_sampler()
    return jax.vmap(
        lambda k: make_streams(k, workload.lam, workload.mu,
                               workload.sampler, L=L, K=K, A_max=A_max,
                               horizon=horizon,
                               num_resources=workload.num_resources,
                               fault_rate=fault_rate,
                               repair_rate=repair_rate))(keys)


def _check_divides(G: int, mesh: Mesh) -> None:
    ndev = mesh.devices.size
    if G % ndev:
        raise ValueError(
            f"ensemble size G={G} must divide evenly over the {ndev}-device "
            f"mesh (equal per-device shards); pad the key batch or change "
            "the device count")


#: Memoized shard_mapped+jitted runners.  ``shard_map`` re-traces (and the
#: surrounding jit recompiles) whenever it is handed a NEW closure, so
#: building one per call would pay full compilation on EVERY
#: ``monte_carlo_policy(..., mesh=)`` invocation; caching on the launch
#: identity — workload (frozen dataclass), policy, engine, mesh, sorted
#: config — makes repeated sharded launches as cheap as the unsharded
#: engines' own jit caches.
_RUNNERS: dict = {}


def _sharded_runner(workload, *, spec, mesh, engine, config):
    axis = mesh.axis_names[0]

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis), check_vma=False)
    def run(local_keys):
        return spec.monte_carlo(workload, local_keys, engine=engine,
                                **config)

    return jax.jit(run)


def sharded_monte_carlo(workload, keys, *, policy: str = "bfjs",
                        mesh: Mesh, engine: str = "scan",
                        **config):
    """Run a registered policy's Monte-Carlo with G sharded over ``mesh``.

    Each device runs the unmodified per-policy program
    (``get_policy(policy).monte_carlo``) on its local G/D key shard;
    outputs come back as one global ``(G, ...)`` pytree laid out across
    the mesh.  ``engine="reference"`` ignores the mesh (host-side oracle).
    """
    from .api import get_policy

    spec = get_policy(policy)
    if engine == "reference":
        return spec.monte_carlo(workload, keys, engine=engine, **config)
    _check_divides(int(keys.shape[0]), mesh)
    try:
        cache_key = (workload, policy, engine, mesh,
                     tuple(sorted(config.items())))
        run = _RUNNERS.get(cache_key)
    except TypeError:           # unhashable config value: run uncached
        cache_key, run = None, None
    if run is None:
        run = _sharded_runner(workload, spec=spec, mesh=mesh,
                              engine=engine, config=config)
        if cache_key is not None:
            _RUNNERS[cache_key] = run
    return run(keys)


def monte_carlo_chunked(workload, keys, *, policy: str = "bfjs",
                        chunk: int, mesh: Mesh | None = None,
                        checkpoint_dir: str | None = None,
                        resume: bool = False,
                        stop_after_chunks: int | None = None,
                        horizon: int = 10_000, fault_rate: float = 0.0,
                        repair_rate: float = 1.0, **config):
    """Crash-safe chunked Monte-Carlo, optionally mesh-sharded.

    Pre-generates the whole ensemble's streams (bit-identical to the
    per-key chains the straight Monte-Carlo path draws), then runs
    ``core.engine.chunked.run_chunked`` with the ensemble axis vmapped —
    and, with ``mesh=``, shard_mapped — inside each chunk.  Checkpoints
    store the full ``(G, ...)`` carry host-side and never pin a device
    count, so ``resume=True`` continues on any mesh whose size divides G.
    """
    if mesh is not None:
        _check_divides(int(keys.shape[0]), mesh)
    streams = ensemble_streams(
        workload, keys, L=config.get("L", 8), K=config.get("K", 16),
        A_max=config.get("A_max", 8), horizon=horizon,
        fault_rate=fault_rate, repair_rate=repair_rate)
    if policy == "bfjs-mr" and "capacity" not in config:
        config["capacity"] = workload.capacity
    from .chunked import run_chunked
    return run_chunked(streams, policy=policy, chunk=chunk, mesh=mesh,
                       checkpoint_dir=checkpoint_dir, resume=resume,
                       stop_after_chunks=stop_after_chunks, **config)
