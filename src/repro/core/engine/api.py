"""Workload-first policy-generic entry points for the accelerator engines.

One registry maps policy names to their engine implementations so every
caller — serving capacity planner, benchmarks, examples, later sharded /
admission-control fleets — dispatches through the same three calls, all
keyed on a first-class :class:`~repro.core.engine.workload.Workload`:

    wl = Workload(lam=1.5, mu=0.01, sampler=sampler)        # R = 1
    run_policy(wl, policy="vqs", engine="scan", key=key, L=8, ...)
    run_policy_streams(streams, policy="vqs", engine="scan", ...)  # traces
    monte_carlo_policy(wl, keys, policy="bfjs", engine="pallas", ...)

``engine`` is always one of ``"reference" | "scan" | "pallas"`` with the
same contract as the BF-J/S stack: "scan" bit-matches "reference" while
``truncated == 0``, and "pallas" bit-matches "scan".  Policy-specific
configuration (``J`` for VQS, ``work_steps`` bounds, ...) passes through as
keyword arguments; unknown keys are rejected by the policy's runner.

Multi-resource workloads (``num_resources=R > 1``, per-resource
``capacity``) and heterogeneous fleets (an ``(L, R)`` per-server
``capacity`` plane) route to ``policy="bfjs-mr"`` — the Tetris-alignment
BF-J/S of paper Section VIII; the single-resource policies reject them
loudly.

The PR 2 loose-argument signatures, ``run_policy(key, lam, mu, sampler,
...)`` / ``monte_carlo_policy(keys, lam, mu, sampler, ...)``, remain as
deprecation shims that build a ``Workload`` internally — bit-match
regression tested (``tests/test_workload_api.py``), so existing callers
keep their exact trajectories while migrating.

New policies register with ``register_policy`` — the hook the roadmap's
sharded-ensemble and admission-control engines plug into.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Callable

import jax

from .bfjs import (monte_carlo_bfjs_workload, run_bfjs_trace,
                   run_bfjs_workload)
from .bfjs_mr import (monte_carlo_bfjs_mr_workload, run_bfjs_mr_trace,
                      run_bfjs_mr_workload)
from .streams import PolicyResult, SchedStreams
from .vqs import monte_carlo_vqs_workload, run_vqs_trace, run_vqs_workload
from .vqs_bf import (monte_carlo_vqs_bf_workload, run_vqs_bf_trace,
                     run_vqs_bf_workload)
from .workload import Workload

ENGINES = ("reference", "scan", "pallas")


@dataclass(frozen=True)
class PolicySpec:
    """Engine implementations of one scheduling policy.

    ``run``/``monte_carlo`` are workload-first: they take a ``Workload``
    and the PRNG key(s); ``run_streams`` takes pre-materialized
    ``SchedStreams`` (randomness already drawn or trace-built), so it needs
    no workload.
    """
    name: str
    run: Callable[..., PolicyResult]          # (workload, key, ...)
    run_streams: Callable[..., PolicyResult]  # (streams, ...)
    monte_carlo: Callable[..., PolicyResult]  # (workload, keys, ...)


_POLICIES: dict[str, PolicySpec] = {}


def register_policy(spec: PolicySpec) -> PolicySpec:
    if spec.name in _POLICIES:
        raise ValueError(f"policy {spec.name!r} already registered")
    _POLICIES[spec.name] = spec
    return spec


def available_policies() -> tuple[str, ...]:
    return tuple(sorted(_POLICIES))


def get_policy(policy: str) -> PolicySpec:
    try:
        return _POLICIES[policy]
    except KeyError:
        raise ValueError(
            f"unknown policy {policy!r}; registered: "
            f"{', '.join(available_policies())}") from None


def _check_engine(engine: str) -> None:
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of "
                         f"{', '.join(ENGINES)}")


register_policy(PolicySpec(
    name="bfjs",
    run=run_bfjs_workload,
    run_streams=run_bfjs_trace,
    monte_carlo=monte_carlo_bfjs_workload,
))

register_policy(PolicySpec(
    name="vqs",
    run=run_vqs_workload,
    run_streams=run_vqs_trace,
    monte_carlo=monte_carlo_vqs_workload,
))

register_policy(PolicySpec(
    name="bfjs-mr",
    run=run_bfjs_mr_workload,
    run_streams=run_bfjs_mr_trace,
    monte_carlo=monte_carlo_bfjs_mr_workload,
))

register_policy(PolicySpec(
    name="vqs-bf",
    run=run_vqs_bf_workload,
    run_streams=run_vqs_bf_trace,
    monte_carlo=monte_carlo_vqs_bf_workload,
))


def _legacy_workload(fn_name: str, legacy: tuple) -> Workload:
    """Build a Workload from the deprecated (lam, mu, sampler) tail."""
    if len(legacy) != 3:
        raise TypeError(
            f"{fn_name} takes a Workload (new API) or the deprecated "
            f"(key, lam, mu, sampler) form; got {1 + len(legacy)} "
            "positional arguments")
    lam, mu, sampler = legacy
    warnings.warn(
        f"{fn_name}(key, lam, mu, sampler, ...) is deprecated; pass a "
        f"Workload: {fn_name}(Workload(lam=lam, mu=mu, sampler=sampler), "
        "key=key, ...)", DeprecationWarning, stacklevel=3)
    return Workload(lam=float(lam), mu=float(mu), sampler=sampler)


def run_policy(workload, *legacy, policy: str = "bfjs",
               engine: str = "scan", key: jax.Array | None = None,
               **config) -> PolicyResult:
    """Simulate one cluster under ``policy`` with the chosen ``engine``.

    ``workload`` is a :class:`Workload` (arrival rate, size sampler,
    service rate, resource count, per-resource capacity); ``key`` — passed
    positionally (``run_policy(wl, key, ...)``, mirroring
    ``monte_carlo_policy``) or as ``key=`` — seeds the pre-generated
    randomness streams (default ``PRNGKey(0)``).  ``config`` passes
    through to the policy runner (``L``, ``K``, ``Qcap``, ``A_max``,
    ``horizon``, ``work_steps``; ``J``/``drain`` for VQS).

    The deprecated positional form ``run_policy(key, lam, mu, sampler,
    ...)`` builds the same Workload internally (bit-identical results) and
    emits a ``DeprecationWarning``.
    """
    _check_engine(engine)
    if not isinstance(workload, Workload):
        legacy_key = workload
        workload = _legacy_workload("run_policy", legacy)
        return get_policy(policy).run(workload, legacy_key, engine=engine,
                                      **config)
    if legacy:
        if len(legacy) != 1 or key is not None:
            raise TypeError(
                "run_policy(workload, key, ...) takes exactly one extra "
                "positional argument (the PRNG key)")
        key = legacy[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    from .tuning import apply_tuned
    apply_tuned(policy, engine, config, workload.num_resources)
    return get_policy(policy).run(workload, key, engine=engine, **config)


def _check_chunked(engine: str, chunk: int | None) -> None:
    if engine != "scan":
        raise ValueError(
            f'checkpointed chunked sweeps need engine="scan" (its carry is '
            f"the entire simulation state); got engine={engine!r}")
    if chunk is None:
        raise ValueError("checkpoint_dir=/resume= need chunk= (the "
                         "boundary interval, in slots)")


def run_policy_streams(streams: SchedStreams, *, policy: str = "bfjs",
                       engine: str = "scan",
                       checkpoint_dir: str | None = None,
                       chunk: int | None = None, resume: bool = False,
                       stop_after_chunks: int | None = None,
                       mesh=None, devices=None, audit: bool = False,
                       **config) -> PolicyResult:
    """Replay explicit streams (e.g. ``streams_from_trace``) through a
    policy engine — the trace-driven path of the stack.  Multi-resource
    streams (``(T, A_max, R)`` sizes, e.g. ``streams_from_trace(trace,
    collapse=False)``) replay through ``policy="bfjs-mr"``.

    ``chunk=``/``checkpoint_dir=`` turn the sweep crash-safe: the streams
    run through the chunk driver, ``stream_policy(iter_stream_chunks(
    streams, chunk))``, which persists the scan engine's complete carry at
    every ``chunk``-slot boundary (atomic rename) so ``resume=True``
    continues a killed sweep BIT-EXACTLY where it stopped (see
    ``core.engine.streaming``).  Only ``engine="scan"`` supports this —
    reference keeps host-side state, pallas keeps VMEM-resident state;
    both are rejected loudly.  Ensemble-batched streams (leading G axis)
    may add ``mesh=``/``devices=`` to shard the ensemble over devices per
    chunk (``core.engine.sharding``).  The result carries the trajectory
    only: the driver's pipeline counters (``chunks_behind``,
    ``host_stall_us``) stay None, as on a one-shot run.

    For streams that are NOT fully materialized — an unbounded arrival
    iterator, a multi-GB trace read chunk-by-chunk — call
    ``core.engine.stream_policy`` on a chunk iterator directly; it
    bit-matches this function on any finite trace.

    ``audit=True`` runs the runtime invariant auditor over the finished
    result (``core.engine.supervisor.audit_result`` — job conservation,
    capacity bounds, fault accounting) and raises a typed
    ``InvariantViolation`` naming the failed counter; it needs explicit
    ``L=``/``K=`` in the config.
    """
    _check_engine(engine)
    from .sharding import resolve_mesh
    from .tuning import apply_tuned
    mesh = resolve_mesh(mesh, devices)
    apply_tuned(policy, engine, config, streams.num_resources)
    audit_cfg = dict(config)

    def _audited(res: PolicyResult) -> PolicyResult:
        if audit:
            from .supervisor import audit_result
            audit_result(streams, res, policy=policy, config=audit_cfg)
        return res

    if chunk is not None or checkpoint_dir is not None or resume:
        _check_chunked(engine, chunk)
        from .streaming import iter_stream_chunks, stream_policy
        res = stream_policy(
            iter_stream_chunks(streams, chunk), policy=policy,
            checkpoint_dir=checkpoint_dir, resume=resume,
            stop_after_chunks=stop_after_chunks, mesh=mesh, **config)
        return _audited(res._replace(chunks_behind=None,
                                     host_stall_us=None))
    if mesh is not None:
        raise ValueError(
            "mesh=/devices= on run_policy_streams needs the chunked path "
            "(chunk=); for straight sharded Monte-Carlo use "
            "monte_carlo_policy(..., mesh=)")
    return _audited(get_policy(policy).run_streams(streams, engine=engine,
                                                   **config))


def monte_carlo_policy(workload, *legacy, policy: str = "bfjs",
                       engine: str = "scan",
                       keys: jax.Array | None = None,
                       mesh=None, devices=None,
                       chunk: int | None = None,
                       checkpoint_dir: str | None = None,
                       resume: bool = False,
                       stop_after_chunks: int | None = None,
                       **config) -> PolicyResult:
    """One simulated cluster per key; "pallas" runs the ensemble as the
    kernel grid, other engines vmap (the host-side oracles loop).

    New API: ``monte_carlo_policy(workload, keys, policy=..., ...)`` (or
    ``keys=`` by keyword).  The deprecated ``monte_carlo_policy(keys, lam,
    mu, sampler, ...)`` form is a bit-match shim.

    ``mesh=`` (a 1-D ``jax.sharding.Mesh``) or ``devices=`` (an int or
    device list) shards the ensemble dimension over devices — bit-identical
    to the single-device run, one G/D shard per device
    (``core.engine.sharding``; ``engine="reference"`` is host-side and
    ignores the mesh).  ``chunk=``/``checkpoint_dir=``/``resume=`` run the
    sweep crash-safe in T-chunks through ``run_policy_streams``' chunk
    driver (scan engine only), composing with the mesh; checkpoints never
    pin a device count, so a sweep may resume on a different mesh size.
    """
    _check_engine(engine)
    if not isinstance(workload, Workload):
        legacy_keys = workload
        workload = _legacy_workload("monte_carlo_policy", legacy)
        return get_policy(policy).monte_carlo(workload, legacy_keys,
                                              engine=engine, **config)
    if legacy:
        if len(legacy) != 1 or keys is not None:
            raise TypeError(
                "monte_carlo_policy(workload, keys, ...) takes exactly one "
                "extra positional argument (the key batch)")
        keys = legacy[0]
    if keys is None:
        raise TypeError("monte_carlo_policy needs keys= (one PRNG key per "
                        "ensemble member)")
    from .sharding import (monte_carlo_chunked, resolve_mesh,
                           sharded_monte_carlo)
    from .tuning import apply_tuned
    mesh = resolve_mesh(mesh, devices)
    apply_tuned(policy, engine, config, workload.num_resources)
    if chunk is not None or checkpoint_dir is not None or resume:
        _check_chunked(engine, chunk)
        return monte_carlo_chunked(workload, keys, policy=policy,
                                   chunk=chunk, mesh=mesh,
                                   checkpoint_dir=checkpoint_dir,
                                   resume=resume,
                                   stop_after_chunks=stop_after_chunks,
                                   **config)
    if mesh is not None:
        return sharded_monte_carlo(workload, keys, policy=policy,
                                   mesh=mesh, engine=engine, **config)
    return get_policy(policy).monte_carlo(workload, keys, engine=engine,
                                          **config)
