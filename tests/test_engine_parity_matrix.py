"""Cross-engine parity matrix: every (policy, engine) cell must reproduce
the policy's reference trajectory on a shared seed.

One parametrized sweep over policy x engine so every future engine lands
with parity enforced by collection, not convention: registering a policy
(or growing ENGINES) grows the matrix automatically, and a cell that
cannot run is a FAILURE, not a skip.  The uncollapsed google_like_50 CSV
fixture closes the loop for the trace-driven path (real-trace columns ->
streams -> scan == pallas == oracle)."""
import os

import jax
import numpy as np
import pytest

from repro.core import load_trace_csv
from repro.core.engine import (ENGINES, Workload, available_policies,
                               run_policy, run_policy_streams,
                               streams_from_trace)

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "google_like_50.csv")


def _scalar_sampler(key, n):
    return jax.random.uniform(key, (n,), minval=0.05, maxval=0.5)


def _vec_sampler(key, n):
    return jax.random.uniform(key, (n, 2), minval=0.05, maxval=0.5)


#: policy -> (Workload, engine-agnostic config).  K >= 2^J for VQS (the
#: packing bound), generous work_steps everywhere so truncated == 0 and
#: the bit-match contract applies end to end.
MATRIX = {
    "bfjs": (Workload(lam=1.2, mu=0.05, sampler=_scalar_sampler),
             dict(L=4, K=6, Qcap=64, A_max=5, horizon=150)),
    "vqs": (Workload(lam=1.0, mu=0.05, sampler=_scalar_sampler),
            dict(L=4, K=8, Qcap=64, A_max=5, horizon=150, J=3)),
    "bfjs-mr": (Workload(lam=0.5, mu=0.05, sampler=_vec_sampler,
                         num_resources=2, capacity=(1.0, 0.75)),
                dict(L=4, K=8, Qcap=64, A_max=5, horizon=150,
                     work_steps=24)),
    # vqs-bf places ONE job per work step (largest-fit pops can't batch),
    # so its bound is sized to the burst, not to A_max
    "vqs-bf": (Workload(lam=1.0, mu=0.05, sampler=_scalar_sampler),
               dict(L=4, K=8, Qcap=64, A_max=5, horizon=150, J=3,
                    work_steps=48)),
}


def test_matrix_covers_every_registered_policy():
    assert set(MATRIX) == set(available_policies()), (
        "every registered policy must appear in the parity matrix — add "
        "its Workload/config row here when registering a new policy")


@pytest.fixture(scope="module")
def reference_runs():
    """One reference trajectory per policy, computed once and shared."""
    key = jax.random.PRNGKey(42)
    return {policy: run_policy(wl, key, policy=policy, engine="reference",
                               **{k: v for k, v in cfg.items()
                                  if k != "work_steps"})
            for policy, (wl, cfg) in MATRIX.items()}


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("policy", sorted(MATRIX))
def test_policy_engine_parity(policy, engine, reference_runs):
    wl, cfg = MATRIX[policy]
    res = run_policy(wl, jax.random.PRNGKey(42), policy=policy,
                     engine=engine, **cfg)
    ref = reference_runs[policy]
    assert int(np.asarray(res.truncated).sum()) == 0
    np.testing.assert_array_equal(np.asarray(res.queue_len),
                                  np.asarray(ref.queue_len))
    np.testing.assert_array_equal(np.asarray(res.occupancy),
                                  np.asarray(ref.occupancy))
    np.testing.assert_array_equal(np.asarray(res.departed),
                                  np.asarray(ref.departed))
    np.testing.assert_array_equal(np.asarray(res.dropped),
                                  np.asarray(ref.dropped))


# ---------------------------------------------------------------------------
# trace-driven parity: the uncollapsed google_like_50 CSV fixture
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def google50_streams():
    trace = load_trace_csv(FIXTURE, slot_seconds=10.0)
    return streams_from_trace(trace, collapse=False, num_resources=2)


@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_google50_uncollapsed_trace_parity(engine, google50_streams):
    """The ISSUE acceptance path: the real-columns google_like_50 trace
    replays UNCOLLAPSED through every accelerator engine and bit-matches
    the event-driven oracle with truncated == 0."""
    kw = dict(L=8, K=16, Qcap=128, work_steps=32)
    res = run_policy_streams(google50_streams, policy="bfjs-mr",
                             engine=engine, **kw)
    ref = run_policy_streams(google50_streams, policy="bfjs-mr",
                             engine="reference", L=8)
    assert int(res.truncated) == 0 and int(res.dropped) == 0
    np.testing.assert_array_equal(np.asarray(res.queue_len),
                                  np.asarray(ref.queue_len))
    np.testing.assert_array_equal(np.asarray(res.occupancy),
                                  np.asarray(ref.occupancy))
    np.testing.assert_array_equal(np.asarray(res.departed),
                                  np.asarray(ref.departed))
    assert int(res.departed[-1]) > 0


# ---------------------------------------------------------------------------
# stream-driven parity: every policy's reference on the engines' own streams
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", sorted(MATRIX))
def test_stream_reference_parity(policy):
    """``run_policy_streams(engine="reference")`` on ``make_streams``
    output equals the scan and pallas engines on the same streams: the
    comparison the chip smoke run makes, with no in-loop draw in it."""
    from repro.core.engine import make_streams
    wl, cfg = MATRIX[policy]
    cfg = dict(cfg)
    horizon = cfg.pop("horizon")
    st = make_streams(jax.random.PRNGKey(7), wl.lam, wl.mu, wl.sampler,
                      L=cfg["L"], K=cfg["K"], A_max=cfg["A_max"],
                      horizon=horizon, num_resources=wl.num_resources)
    ref_cfg = {k: v for k, v in cfg.items() if k != "work_steps"}
    if policy == "bfjs-mr":
        st = jax.device_get(st)
        ref_cfg["capacity"] = cfg["capacity"] = wl.capacity
    ref = run_policy_streams(st, policy=policy, engine="reference",
                             **ref_cfg)
    assert int(ref.departed[-1]) > 0
    for engine in ("scan", "pallas"):
        res = run_policy_streams(st, policy=policy, engine=engine, **cfg)
        assert int(res.truncated) == 0
        for f in ("queue_len", "occupancy", "departed", "dropped"):
            np.testing.assert_array_equal(np.asarray(getattr(res, f)),
                                          np.asarray(getattr(ref, f)))
