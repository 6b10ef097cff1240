"""Accelerator-resident scheduling engines (pure JAX + Pallas kernels).

The event-driven numpy engine (core/simulator.py) is exact and fast on
hosts; this package re-expresses the paper's schedulers as fixed-shape,
branch-free programs that run ON the accelerator:

  * ``workload`` — the first-class :class:`Workload` spec (arrival rate,
    size sampler, service rate, ``num_resources``, per-resource or
    per-server capacity) every entry point dispatches on;
  * ``streams``  — pre-generated randomness (``SchedStreams``), from PRNG
    keys (``make_streams``) or workload traces (``streams_from_trace``),
    with ``(T, A_max, R)`` requirement vectors when R > 1;
  * ``ops``      — jit/vmap-friendly primitive ops (Best-Fit placement,
    max-weight configurations, exact partition-I classification, the f32
    Tetris alignment score);
  * ``bfjs``     — the single-resource BF-J/S engines (PR 1);
  * ``vqs``      — the VQS engines (paper Section V);
  * ``vqs_bf``   — the VQS-BF engines (paper Section VI — VQS throughput
    with BF-like delay via largest-fit-first bucketed rings),
    ``policy="vqs-bf"``;
  * ``bfjs_mr``  — the multi-resource Tetris-alignment BF-J/S engines
    (paper Section VIII), ``policy="bfjs-mr"``;
  * ``api``      — the policy registry behind ``run_policy(workload, ...)``
    (the PR 2 loose-argument forms remain as deprecation shims);
  * ``sharding`` — the ensemble dimension G on a device mesh
    (``monte_carlo_policy(..., mesh=|devices=)``, bit-identical to the
    single-device run; composes with checkpointed chunked sweeps);
  * ``tuning``   — the shape-keyed ``window=``/``work_steps=`` autotuner
    with its persistent, bit-match-verified tuning cache
    (``REPRO_TUNING_CACHE``);
  * ``streaming`` — the one chunk driver: ``stream_policy`` drives chunks
    of any (possibly infinite) arrival iterator through the stateful scan
    engines (``chunked._STATEFUL``) with carried state, double-buffering
    host ingestion against device compute (backpressure counters on
    ``PolicyResult``) and checkpointing every boundary on request; finite
    traces replay bit-identically to the one-shot run under any chunking,
    and the checkpointed sweeps of ``run_policy_streams(chunk=)`` /
    ``monte_carlo_policy(chunk=)`` run through it;
  * ``supervisor`` — the self-healing layer around the streaming loop
    (``stream_policy(supervisor=Supervisor(...))``): retry with jittered
    backoff on transient ingestion/staging/checkpoint failures, watchdog
    timeouts, rollback over corrupt checkpoints, poison-chunk quarantine,
    and the opt-in jitted runtime invariant auditor (``audit=True``,
    ``audit_result``) — DESIGN.md §14.

Engine contract (DESIGN.md §1): per policy, ``"scan"`` bit-matches
``"reference"`` while ``truncated == 0``, and ``"pallas"`` bit-matches
``"scan"`` — asserted by tests/test_jax_sched.py, tests/test_vqs_engine.py,
tests/test_mr_engine.py, tests/test_kernels.py and, for every registered
(policy, engine) cell at once, tests/test_engine_parity_matrix.py.
"""
from .api import (ENGINES, PolicySpec, available_policies, get_policy,
                  monte_carlo_policy, register_policy, run_policy,
                  run_policy_streams)
from .bfjs import (BFJSResult, BFJSState, DEFAULT_MAX_REQUEUE,
                   monte_carlo_bfjs, run_bfjs, run_bfjs_streams,
                   run_bfjs_trace)
from .bfjs_mr import (monte_carlo_bfjs_mr_workload, run_bfjs_mr_streams,
                      run_bfjs_mr_trace, run_bfjs_mr_workload)
from .streaming import (iter_stream_chunks, stream_chunks_from_trace,
                        stream_policy, streams_fingerprint)
from .supervisor import (INVARIANTS, CheckpointRollbackWarning,
                         InvariantViolation, RetryPolicy, Supervisor,
                         SupervisorError, SupervisorTimeout,
                         SupervisorWarning, audit_result, make_auditor)
from .sharding import (ENSEMBLE_AXIS, ensemble_streams, monte_carlo_chunked,
                       resolve_mesh, sharded_monte_carlo)
from .tuning import (TuningCache, apply_tuned, autotune, shape_key,
                     tuning_enabled)
from .ops import (alignment_score_pair_jnp, best_fit_place, best_fit_server,
                  k_red_jnp, largest_fitting_job, max_weight_config_jax,
                  vq_type_of, vq_type_of_grid)
from .streams import (BFJSStreams, INF_SLOT, PolicyResult, SchedStreams,
                      fault_plane_from_events, make_fault_plane,
                      make_streams, resolve_work_steps, streams_from_trace,
                      with_fault_plane)
from .vqs import (monte_carlo_vqs, run_vqs, run_vqs_streams, run_vqs_trace)
from .vqs_bf import (monte_carlo_vqs_bf, run_vqs_bf, run_vqs_bf_streams,
                     run_vqs_bf_trace)
from .workload import Workload

__all__ = [
    "ENGINES", "PolicySpec", "available_policies", "get_policy",
    "monte_carlo_policy", "register_policy", "run_policy",
    "run_policy_streams", "BFJSResult", "BFJSState", "DEFAULT_MAX_REQUEUE",
    "monte_carlo_bfjs", "run_bfjs", "run_bfjs_streams", "run_bfjs_trace",
    "monte_carlo_bfjs_mr_workload", "run_bfjs_mr_streams",
    "run_bfjs_mr_trace", "run_bfjs_mr_workload",
    "streams_fingerprint", "iter_stream_chunks",
    "stream_chunks_from_trace", "stream_policy",
    "INVARIANTS", "CheckpointRollbackWarning", "InvariantViolation",
    "RetryPolicy", "Supervisor", "SupervisorError", "SupervisorTimeout",
    "SupervisorWarning", "audit_result", "make_auditor",
    "ENSEMBLE_AXIS", "ensemble_streams",
    "monte_carlo_chunked", "resolve_mesh", "sharded_monte_carlo",
    "TuningCache", "apply_tuned", "autotune", "shape_key",
    "tuning_enabled", "alignment_score_pair_jnp",
    "best_fit_place", "best_fit_server", "k_red_jnp", "largest_fitting_job",
    "max_weight_config_jax", "vq_type_of", "vq_type_of_grid", "BFJSStreams",
    "INF_SLOT", "PolicyResult", "SchedStreams", "fault_plane_from_events",
    "make_fault_plane", "make_streams", "resolve_work_steps",
    "streams_from_trace", "with_fault_plane", "monte_carlo_vqs",
    "run_vqs", "run_vqs_streams", "run_vqs_trace", "monte_carlo_vqs_bf",
    "run_vqs_bf", "run_vqs_bf_streams", "run_vqs_bf_trace", "Workload",
]
