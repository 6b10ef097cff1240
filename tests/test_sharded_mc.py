"""Mesh-sharded Monte-Carlo + the shape-keyed tuning cache (DESIGN.md §11).

Sharding contract: ``monte_carlo_policy(..., mesh=|devices=)`` is
BIT-IDENTICAL to the unsharded run for every registered policy x engine —
each device consumes exactly its own key shard, so the per-member chains
never change.  Verified in-process on a devices=1 mesh (shard_map active,
same partitioning code path) for the full matrix, and in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for real
multi-device placement — including a sweep checkpointed on 4 devices and
resumed on 2 (checkpoints never pin a device count).

Tuning contract: the persistent JSON cache round-trips winners keyed by
launch shape, ignores corrupt/stale files loudly, only fills knobs the
caller left unset, and ``autotune`` never caches a winner whose trajectory
is not bit-identical to the untuned baseline.  The suite runs under
``REPRO_TUNING_CACHE=off`` (conftest) so these tests opt in explicitly via
monkeypatched paths — a user's real cache is never read or written.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

# deselected by the fast tier-1 lane (-m "not slow"); CI runs
# the full suite
pytestmark = pytest.mark.slow

from repro.core.engine import (TuningCache, Workload, apply_tuned, autotune,
                               make_streams, monte_carlo_policy,
                               resolve_mesh, run_policy_streams, shape_key,
                               tuning_enabled)
from repro.core.engine.sharding import ENSEMBLE_AXIS
from repro.serving.engine import estimate_capacity

G = 4


def _scalar_sampler(key, n):
    return jax.random.uniform(key, (n,), minval=0.05, maxval=0.5)


def _vec_sampler(key, n):
    return jax.random.uniform(key, (n, 2), minval=0.05, maxval=0.5)


#: policy -> (Workload, config): the parity-matrix shapes, shrunk to a
#: 96-slot horizon so the pallas cells stay fast in interpret mode.
MATRIX = {
    "bfjs": (Workload(lam=1.2, mu=0.05, sampler=_scalar_sampler),
             dict(L=4, K=6, Qcap=64, A_max=5, horizon=96)),
    "vqs": (Workload(lam=1.0, mu=0.05, sampler=_scalar_sampler),
            dict(L=4, K=8, Qcap=64, A_max=5, horizon=96, J=3)),
    "bfjs-mr": (Workload(lam=0.5, mu=0.05, sampler=_vec_sampler,
                         num_resources=2, capacity=(1.0, 0.75)),
                dict(L=4, K=8, Qcap=64, A_max=5, horizon=96,
                     work_steps=24)),
}


def _keys(n=G):
    return jax.random.split(jax.random.PRNGKey(5), n)


def _assert_bitmatch(res, ref, msg):
    for f in ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(res, f)), np.asarray(getattr(ref, f)),
            err_msg=f"{msg}: field {f!r}")


# ---------------------------------------------------------------------------
# sharded == unsharded, full policy x engine matrix (1-device mesh:
# shard_map active, identical partitioning code path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ("reference", "scan", "pallas"))
@pytest.mark.parametrize("policy", sorted(MATRIX))
def test_mesh_parity_every_policy_engine(policy, engine):
    wl, cfg = MATRIX[policy]
    ref = monte_carlo_policy(wl, _keys(), policy=policy, engine=engine,
                             **cfg)
    res = monte_carlo_policy(wl, _keys(), policy=policy, engine=engine,
                             devices=1, **cfg)
    assert int(np.asarray(res.truncated).sum()) == 0
    _assert_bitmatch(res, ref, f"{policy}/{engine}: mesh != unsharded")


def test_chunked_mesh_parity_and_resume(tmp_path):
    """chunk= + mesh= composes: the chunked sharded sweep equals the
    straight Monte-Carlo, and a checkpoint taken mid-sweep resumes to the
    exact same trajectory (device count re-chosen at resume time)."""
    wl, cfg = MATRIX["bfjs"]
    full = monte_carlo_policy(wl, _keys(), policy="bfjs", engine="scan",
                              **cfg)
    chunked = monte_carlo_policy(wl, _keys(), policy="bfjs", engine="scan",
                                 devices=1, chunk=32, **cfg)
    _assert_bitmatch(chunked, full, "chunked+mesh != straight MC")
    d = str(tmp_path)
    monte_carlo_policy(wl, _keys(), policy="bfjs", engine="scan", devices=1,
                       chunk=32, checkpoint_dir=d, stop_after_chunks=1,
                       **cfg)
    res = monte_carlo_policy(wl, _keys(), policy="bfjs", engine="scan",
                             chunk=32, checkpoint_dir=d, resume=True, **cfg)
    _assert_bitmatch(res, full, "resume (mesh -> no mesh) diverged")


# ---------------------------------------------------------------------------
# mesh resolution / validation
# ---------------------------------------------------------------------------
def test_resolve_mesh_validation():
    assert resolve_mesh() is None
    m = resolve_mesh(devices=1)
    assert m.axis_names == (ENSEMBLE_AXIS,) and m.devices.size == 1
    assert resolve_mesh(mesh=m) is m
    with pytest.raises(ValueError, match="not both"):
        resolve_mesh(mesh=m, devices=1)
    from jax.sharding import Mesh
    mesh2d = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("a", "b"))
    with pytest.raises(ValueError, match="1-D mesh"):
        resolve_mesh(mesh=mesh2d)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        resolve_mesh(devices=4096)


def test_streams_mesh_needs_chunk():
    streams = make_streams(jax.random.PRNGKey(0), 1.2, 0.05,
                           _scalar_sampler, L=4, K=6, A_max=5, horizon=96)
    with pytest.raises(ValueError, match="chunk"):
        run_policy_streams(streams, policy="bfjs", engine="scan", devices=1,
                           L=4, K=6, Qcap=64, A_max=5)
    from repro.core.engine.chunked import run_chunked
    with pytest.raises(ValueError, match="ensemble-batched"):
        run_chunked(streams, policy="bfjs", chunk=32,
                    mesh=resolve_mesh(devices=1), L=4, K=6, Qcap=64,
                    A_max=5)


# ---------------------------------------------------------------------------
# real multi-device placement (forced 4-device CPU subprocess: XLA_FLAGS
# must be set before jax imports, so this cannot run in-process)
# ---------------------------------------------------------------------------
_CHILD = """
import tempfile
import jax
import numpy as np
assert jax.device_count() >= 4, jax.devices()
from repro.core.engine import Workload, monte_carlo_policy

def scalar(key, n):
    return jax.random.uniform(key, (n,), minval=0.05, maxval=0.5)

def vec(key, n):
    return jax.random.uniform(key, (n, 2), minval=0.05, maxval=0.5)

MATRIX = {
    "bfjs": (Workload(lam=1.2, mu=0.05, sampler=scalar),
             dict(L=4, K=6, Qcap=64, A_max=5, horizon=96)),
    "vqs": (Workload(lam=1.0, mu=0.05, sampler=scalar),
            dict(L=4, K=8, Qcap=64, A_max=5, horizon=96, J=3)),
    "bfjs-mr": (Workload(lam=0.5, mu=0.05, sampler=vec, num_resources=2,
                         capacity=(1.0, 0.75)),
                dict(L=4, K=8, Qcap=64, A_max=5, horizon=96,
                     work_steps=24)),
}
keys = jax.random.split(jax.random.PRNGKey(5), 4)

def bitmatch(a, b, msg):
    for f in a._fields:
        assert (np.asarray(getattr(a, f))
                == np.asarray(getattr(b, f))).all(), (msg, f)

for policy, (wl, cfg) in MATRIX.items():
    for engine in ("reference", "scan", "pallas"):
        ref = monte_carlo_policy(wl, keys, policy=policy, engine=engine,
                                 **cfg)
        res = monte_carlo_policy(wl, keys, policy=policy, engine=engine,
                                 devices=4, **cfg)
        bitmatch(res, ref, f"{policy}/{engine}")

# a key batch that does not divide the mesh is rejected loudly
wl, cfg = MATRIX["bfjs"]
try:
    monte_carlo_policy(wl, jax.random.split(jax.random.PRNGKey(1), 6),
                       policy="bfjs", engine="scan", devices=4, **cfg)
except ValueError as e:
    assert "divide evenly" in str(e), e
else:
    raise SystemExit("non-dividing G was not rejected")

# checkpoint on 4 devices -> resume on 2: bit-exact vs straight-through
d = tempfile.mkdtemp()
full = monte_carlo_policy(wl, keys, policy="bfjs", engine="scan", **cfg)
monte_carlo_policy(wl, keys, policy="bfjs", engine="scan", devices=4,
                   chunk=32, checkpoint_dir=d, stop_after_chunks=1, **cfg)
res = monte_carlo_policy(wl, keys, policy="bfjs", engine="scan", devices=2,
                         chunk=32, checkpoint_dir=d, resume=True, **cfg)
bitmatch(res, full, "4-device checkpoint resumed on 2 devices")
print("OK")
"""


def test_multi_device_parity_and_cross_device_resume():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["REPRO_TUNING_CACHE"] = "off"
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().endswith("OK"), proc.stdout


# ---------------------------------------------------------------------------
# tuning cache: round-trip, corruption, fill semantics
# ---------------------------------------------------------------------------
def test_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "c.json"))
    assert tuning_enabled()
    key = shape_key("bfjs", "scan", L=4, K=8, R=1, Qcap=64, A_max=6)
    TuningCache().put(key, {"work_steps": 5, "window": None})
    assert TuningCache().get(key)["work_steps"] == 5
    # atomic writes leave no tmp droppings, and the file is valid JSON
    assert [p for p in os.listdir(tmp_path) if p.endswith(".tmp")] == []
    with open(tmp_path / "c.json") as f:
        assert json.load(f)["entries"][key]["work_steps"] == 5


def test_cache_off_disables_everything(monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", "off")
    assert not tuning_enabled()
    cfg = dict(L=4, K=8, Qcap=64, A_max=6)
    assert apply_tuned("bfjs", "scan", cfg) \
        == {"tuned": 0, "cache_hit": 0}
    assert "work_steps" not in cfg
    with pytest.raises(ValueError, match="disabled"):
        autotune(Workload(lam=1.0, mu=0.05, sampler=_scalar_sampler),
                 _keys(2), policy="bfjs", engine="scan", **cfg)


def test_corrupt_and_stale_caches_ignored(tmp_path, monkeypatch):
    path = tmp_path / "c.json"
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(path))
    path.write_text("{definitely not json")
    with pytest.warns(UserWarning, match="corrupt"):
        assert TuningCache().load() == {}
    path.write_text(json.dumps(
        {"schema": "tuning.v0", "entries": {"k": {"work_steps": 1}}}))
    with pytest.warns(UserWarning, match="schema"):
        assert TuningCache().load() == {}
    # the next store overwrites the bad file with a fresh valid cache
    with pytest.warns(UserWarning, match="schema"):
        TuningCache().put("k", {"work_steps": 3})
    assert TuningCache().get("k")["work_steps"] == 3


def test_apply_tuned_fill_semantics(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "c.json"))
    shape = dict(L=4, K=8, R=1, Qcap=64, A_max=6)
    entry = {"work_steps": 4, "window": 48}
    for engine in ("scan", "pallas"):
        TuningCache().put(shape_key("bfjs", engine, **shape), entry)
    # scan: work_steps filled, window never (not a scan knob)
    cfg = dict(L=4, K=8, Qcap=64, A_max=6)
    assert apply_tuned("bfjs", "scan", cfg) \
        == {"tuned": 1, "cache_hit": 1}
    assert cfg["work_steps"] == 4 and "window" not in cfg
    # pallas: both knobs filled
    cfg = dict(L=4, K=8, Qcap=64, A_max=6)
    assert apply_tuned("bfjs", "pallas", cfg) \
        == {"tuned": 1, "cache_hit": 1}
    assert cfg["work_steps"] == 4 and cfg["window"] == 48
    # an explicit value always wins over the cache
    cfg = dict(L=4, K=8, Qcap=64, A_max=6, work_steps=9)
    assert apply_tuned("bfjs", "scan", cfg) \
        == {"tuned": 0, "cache_hit": 1}
    assert cfg["work_steps"] == 9
    # reference has no launch knobs: bypassed entirely
    cfg = dict(L=4, K=8, Qcap=64, A_max=6)
    assert apply_tuned("bfjs", "reference", cfg) \
        == {"tuned": 0, "cache_hit": 0}
    # a different shape misses
    cfg = dict(L=16, K=8, Qcap=64, A_max=6)
    assert apply_tuned("bfjs", "scan", cfg) \
        == {"tuned": 0, "cache_hit": 0}


# ---------------------------------------------------------------------------
# autotune: verified winners only, picked up end-to-end
# ---------------------------------------------------------------------------
def test_autotune_caches_verified_winner_end_to_end(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "c.json"))
    wl, cfg = MATRIX["bfjs"]
    keys = _keys(2)
    out = autotune(wl, keys, policy="bfjs", engine="scan",
                   work_steps_grid=(1, 3, 24), rounds=1, **cfg)
    assert out["key"] == shape_key(
        "bfjs", "scan", L=4, K=6, R=1, Qcap=64, A_max=5)
    entry = TuningCache().get(out["key"])
    assert entry is not None and entry["speedup"] >= 1.0
    # the cached winner reproduces the default trajectory bit-for-bit
    # when injected by the normal monte_carlo_policy path
    tuned = monte_carlo_policy(wl, keys, policy="bfjs", engine="scan",
                               **cfg)
    monkeypatch.setenv("REPRO_TUNING_CACHE", "off")
    default = monte_carlo_policy(wl, keys, policy="bfjs", engine="scan",
                                 **cfg)
    _assert_bitmatch(tuned, default, "tuned run != default run")


def test_autotune_refusals(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(tmp_path / "c.json"))
    wl, cfg = MATRIX["bfjs"]
    with pytest.raises(ValueError, match="no launch knobs"):
        autotune(wl, _keys(2), policy="bfjs", engine="reference", **cfg)
    from repro.kernels.common import interpret_default
    if interpret_default():    # off-TPU: interpret timings refused
        with pytest.raises(ValueError, match="interpret"):
            autotune(wl, _keys(2), policy="bfjs", engine="pallas", **cfg)


# ---------------------------------------------------------------------------
# kernel early exit + serving telemetry
# ---------------------------------------------------------------------------
def test_mr_kernel_early_exit_bit_parity():
    """The bfjs-mr work-list early exit is bit-identical to the full
    fori_loop launch (post-done steps are no-ops by construction)."""
    from repro.kernels.bfjs_mr.ops import bfjs_mr_simulate
    keys = _keys(2)
    streams = jax.vmap(lambda k: make_streams(
        k, 0.5, 0.05, _vec_sampler, L=4, K=8, A_max=5, horizon=96,
        num_resources=2))(keys)
    kw = dict(L=4, K=8, Qcap=64, A_max=5, work_steps=24)
    on = bfjs_mr_simulate(streams, **kw)
    off = bfjs_mr_simulate(streams, early_exit=False, **kw)
    assert int(np.asarray(on.truncated).sum()) == 0
    # the fit search runs once for each work step run, early exit or not
    assert (np.asarray(on.fit_chunks) < np.asarray(off.fit_chunks)).all()
    _assert_bitmatch(on._replace(fit_chunks=None),
                     off._replace(fit_chunks=None),
                     "early_exit=True != early_exit=False")


def test_estimate_capacity_reports_launch_fields():
    out = estimate_capacity(4, 0.5, 20.0, ensembles=2, horizon=64, K=6,
                            Qcap=64, A_max=5)
    assert out["devices"] == 1
    # conftest pins REPRO_TUNING_CACHE=off: attributably untuned
    assert out["tuned"] == 0 and out["cache_hit"] == 0
    assert out["truncated"] == 0
