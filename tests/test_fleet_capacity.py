"""Per-server capacity planes for ``bfjs-mr``: on mixed fleets the
event-driven oracle, the scan engine, the fused kernel (interpret mode off
the TPU) and the benchmark's plain reference agree bit for bit; an
all-equal plane reproduces the tuple capacity it replaces; the
single-resource policies reject a plane; the streaming paths and the
audit take it."""
import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.core.engine import (Workload, make_streams, monte_carlo_policy,
                               run_policy, run_policy_streams, stream_policy)
from repro.core.engine.workload import capacity_plane, normalize_capacity

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_reference():
    path = os.path.join(ROOT, "bench", "reference", "bfjs_mr.py")
    spec = importlib.util.spec_from_file_location("bench_ref_bfjs_mr", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench_reference()

#: Two small mixed fleets: several (cpu, mem) classes in a shuffled order,
#: one machine (mem 0.03) too small for any job of the demand law.
FLEETS = {
    "google-like": [(0.5, 0.5)] * 5 + [(0.5, 0.25)] * 3 + [(1.0, 1.0)] * 2
    + [(0.25, 0.25), (0.5, 0.03)],
    "skewed": [(1.0, 0.5), (0.5, 1.0), (0.75, 0.75), (0.5, 0.03),
               (0.25, 0.5), (1.0, 1.0), (0.5, 0.75), (0.75, 0.25)],
}
KW = dict(K=20, Qcap=128, A_max=6, work_steps=48)
T = 240


def _plane(name):
    rows = np.asarray(FLEETS[name])
    return rows[np.random.default_rng(7).permutation(len(rows))]


def _sampler(key, n):
    return jax.random.uniform(key, (n, 2), minval=0.05, maxval=0.45)


def _workload(caps, lam=0.9):
    return Workload(lam=lam, mu=0.05, sampler=_sampler, num_resources=2,
                    capacity=caps)


def _streams(caps, seed):
    return make_streams(jax.random.PRNGKey(seed), 0.9, 0.05, _sampler,
                        L=len(caps), K=KW["K"], A_max=KW["A_max"],
                        horizon=T, num_resources=2)


def _assert_same(a, b, fields=None):
    # fit_chunks is the kernel's own work counter; no other engine has one
    for f in fields or [f for f in a._fields if f != "fit_chunks"]:
        x, y = getattr(a, f), getattr(b, f)
        assert (x is None) == (y is None), f
        if x is not None:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("fleet", sorted(FLEETS))
def test_engines_and_bench_reference_agree_on_mixed_fleets(fleet, seed):
    caps = _plane(fleet)
    st = _streams(caps, seed)
    L = len(caps)
    oracle = run_policy_streams(st, policy="bfjs-mr", engine="reference",
                                L=L, capacity=caps)
    scan = run_policy_streams(st, policy="bfjs-mr", engine="scan", L=L,
                              capacity=caps, **KW)
    kern = run_policy_streams(st, policy="bfjs-mr", engine="pallas", L=L,
                              capacity=caps, strict=True, window=40, **KW)
    for res in (scan, kern):
        assert int(res.dropped) == 0 and int(res.truncated) == 0
        _assert_same(res, oracle)
    want = REF.simulate(*(np.asarray(x) for x in st[:3]), caps,
                        K=KW["K"], Qcap=KW["Qcap"])
    np.testing.assert_array_equal(np.asarray(scan.queue_len),
                                  want["queue_len"])
    np.testing.assert_array_equal(np.asarray(scan.occupancy),
                                  want["occupancy"])
    np.testing.assert_array_equal(np.asarray(scan.departed),
                                  want["departed"])
    assert want["dropped"] == 0
    assert int(scan.bfs_placements) == want["bfs_placements"] > 0
    # the load makes a queue form
    assert int(np.max(want["queue_len"])) > 0


def test_monte_carlo_entry_point_takes_the_plane():
    """The benchmark cell's call: ``monte_carlo_policy`` on a plane
    workload, every engine, each member equal to the plain reference."""
    caps = _plane("google-like")
    wl = _workload(caps)
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    cfg = dict(L=len(caps), horizon=T, **KW)
    runs = {e: monte_carlo_policy(wl, keys, policy="bfjs-mr", engine=e,
                                  **cfg)
            for e in ("reference", "scan")}
    runs["pallas"] = monte_carlo_policy(wl, keys, policy="bfjs-mr",
                                        engine="pallas", strict=True,
                                        window=40, **cfg)
    _assert_same(runs["scan"], runs["reference"])
    _assert_same(runs["pallas"], runs["scan"])
    for g, k in enumerate(keys):
        st = make_streams(k, wl.lam, wl.mu, _sampler, L=len(caps),
                          K=KW["K"], A_max=KW["A_max"], horizon=T,
                          num_resources=2)
        want = REF.simulate(*(np.asarray(x) for x in st[:3]), caps,
                            K=KW["K"], Qcap=KW["Qcap"])
        for f in ("queue_len", "occupancy", "departed"):
            np.testing.assert_array_equal(
                np.asarray(getattr(runs["pallas"], f)[g]), want[f])
        assert int(runs["pallas"].bfs_placements[g]) \
            == want["bfs_placements"]


@pytest.mark.parametrize("engine", ["reference", "scan", "pallas"])
def test_all_equal_plane_reproduces_the_tuple_capacity(engine):
    key = jax.random.PRNGKey(11)
    cfg = dict(L=6, horizon=160, **KW)
    if engine == "pallas":
        cfg.update(window=40, strict=True)
    tup = run_policy(_workload((1.0, 0.75), lam=0.5), key,
                     policy="bfjs-mr", engine=engine, **cfg)
    plane = run_policy(_workload(np.tile([1.0, 0.75], (6, 1)), lam=0.5),
                       key, policy="bfjs-mr", engine=engine, **cfg)
    _assert_same(plane, tup)


@pytest.mark.parametrize("policy", ["bfjs", "vqs", "vqs-bf"])
def test_single_resource_policies_reject_a_plane(policy):
    wl = Workload(lam=0.5, mu=0.05,
                  sampler=lambda k, n: jax.random.uniform(k, (n,)),
                  capacity=np.ones((4, 1)))
    with pytest.raises(ValueError, match="bfjs-mr"):
        run_policy(wl, jax.random.PRNGKey(0), policy=policy, engine="scan",
                   L=4, K=8, Qcap=32, A_max=4, horizon=20)


def test_plane_is_normalized_and_checked_against_L():
    caps = _plane("skewed")
    wl = _workload(caps)
    assert wl.capacity == normalize_capacity(caps, 2)
    assert hash(wl.capacity) == hash(normalize_capacity(caps.tolist(), 2))
    plane = capacity_plane(wl.capacity, len(caps), 2)
    assert plane.dtype == np.int32 and plane.shape == (len(caps), 2)
    assert plane[list(caps[:, 1]).index(0.03), 1] == round(0.03 * 2 ** 16)
    np.testing.assert_array_equal(capacity_plane((1.0, 0.5), 3, 2),
                                  [[65536, 32768]] * 3)
    with pytest.raises(ValueError, match="rows for L=9"):
        run_policy(wl, jax.random.PRNGKey(0), policy="bfjs-mr",
                   engine="scan", L=9, horizon=20, **KW)
    with pytest.raises(ValueError, match="capacity"):
        _workload(np.ones((4, 3)))
    with pytest.raises(ValueError, match="> 0"):
        _workload(np.zeros((4, 2)))


def test_streaming_paths_take_the_plane():
    """``run_policy_streams`` chunked and ``stream_policy`` thread the
    plane and the new counters across chunks, audited, bit for bit."""
    caps = _plane("google-like")
    st = _streams(caps, 5)
    cfg = dict(L=len(caps), capacity=caps, **KW)
    whole = run_policy_streams(st, policy="bfjs-mr", engine="scan",
                               audit=True, **cfg)
    chunked = run_policy_streams(st, policy="bfjs-mr", engine="scan",
                                 chunk=64, **cfg)
    _assert_same(chunked, whole)

    def chunks():
        for lo in range(0, T, 80):
            yield st._replace(n=st.n[lo:lo + 80], sizes=st.sizes[lo:lo + 80],
                              durs=st.durs[lo:lo + 80])
    streamed = stream_policy(chunks(), policy="bfjs-mr", audit=True, **cfg)
    _assert_same(streamed, whole, fields=("queue_len", "occupancy",
                                          "departed", "dropped", "truncated",
                                          "steps", "bfs_placements"))
    assert int(whole.steps) >= T


def test_work_counters_belong_to_bfjs_mr_alone():
    wl = Workload(lam=0.5, mu=0.05, sampler=lambda k, n: jax.random.uniform(
        k, (n,), minval=0.1, maxval=0.6))
    res = run_policy(wl, jax.random.PRNGKey(0), policy="bfjs",
                     engine="scan", L=4, K=8, Qcap=32, A_max=4, horizon=20)
    assert res.steps is None and res.bfs_placements is None
    assert res.fit_chunks is None
