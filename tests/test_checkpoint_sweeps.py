"""Crash-safe chunked sweeps (DESIGN.md §9).

The resume contract: a sweep interrupted at ANY chunk boundary — cleanly
(``stop_after_chunks``) or by SIGKILL mid-process — and resumed with
``resume=True`` produces a trajectory BIT-IDENTICAL to the uninterrupted
run, on every ``PolicyResult`` field including the fault counters.
Property-tested over random kill schedules, plus a real ``SIGKILL``
delivered from inside the checkpoint writer in a subprocess.

Also pins the satellite contracts: checkpoints refuse to continue a
different sweep (policy / chunk / streams fingerprint), and
``ckpt.save``/``restore`` round-trips every engine-carry dtype
(int32/float32/bool planes, ``(T, R)`` occupancy) bit-exactly.
"""
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# deselected by the fast tier-1 lane (-m "not slow"); CI runs
# the full suite
pytestmark = pytest.mark.slow

# only the kill-schedule property test needs hypothesis — everything else
# in this module must run even where it is not installed
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.checkpoint import ckpt
from repro.core.engine import (iter_stream_chunks, make_streams,
                               run_policy_streams, stream_policy)
from repro.core.engine import streaming
from repro.core.engine.bfjs_mr import run_bfjs_mr_streams

T = 240
FAULT = dict(fault_rate=0.02, repair_rate=0.3)


def _scalar_sampler(key, n):
    return jax.random.uniform(key, (n,), minval=0.1, maxval=0.6)


def _vec_sampler(key, n):
    return jax.random.uniform(key, (n, 2), minval=0.1, maxval=0.5)


#: policy -> (streams, engine config): small faulted sweeps so resume has
#: to carry retry planes, fault counters and ``up_last`` across boundaries.
def _case(policy):
    key = jax.random.PRNGKey(3)
    if policy == "bfjs-mr":
        streams = make_streams(key, 0.6, 0.5, _vec_sampler, L=4, K=3,
                               A_max=4, horizon=T, num_resources=2, **FAULT)
        return streams, dict(L=4, K=3, Qcap=32, A_max=4)
    streams = make_streams(key, 0.6, 0.5, _scalar_sampler, L=4, K=3,
                           A_max=4, horizon=T, **FAULT)
    cfg = dict(L=4, K=3, Qcap=32, A_max=4)
    if policy == "vqs":
        cfg["J"] = 4
    return streams, cfg


@pytest.fixture(scope="module", params=["bfjs", "vqs", "bfjs-mr"])
def case(request):
    policy = request.param
    streams, cfg = _case(policy)
    full = run_policy_streams(streams, policy=policy, engine="scan", **cfg)
    return policy, streams, cfg, full


def _assert_bitmatch(res, full, msg):
    for f in full._fields:
        a, b = np.asarray(getattr(res, f)), np.asarray(getattr(full, f))
        assert a.shape == b.shape and a.dtype == b.dtype, (msg, f)
        np.testing.assert_array_equal(a, b, err_msg=f"{msg}: field {f!r}")


# ---------------------------------------------------------------------------
# interrupt-and-resume == straight-through
# ---------------------------------------------------------------------------
def test_kill_at_boundary_and_resume_bitmatch(case, tmp_path):
    policy, streams, cfg, full = case
    d = str(tmp_path)
    part = run_policy_streams(streams, policy=policy, engine="scan",
                              checkpoint_dir=d, chunk=60,
                              stop_after_chunks=2, **cfg)
    assert part.queue_len.shape[0] == 120   # 2 of 4 chunks ran
    res = run_policy_streams(streams, policy=policy, engine="scan",
                             checkpoint_dir=d, chunk=60, resume=True, **cfg)
    assert int(full.preempted) > 0          # resume crossed real fault state
    _assert_bitmatch(res, full, f"{policy}: resumed != straight-through")
    # resuming a FINISHED sweep returns the stored result, runs nothing
    res2 = run_policy_streams(streams, policy=policy, engine="scan",
                              checkpoint_dir=d, chunk=60, resume=True, **cfg)
    _assert_bitmatch(res2, full, f"{policy}: finished-resume")


_BFJS_STREAMS, _BFJS_CFG = _case("bfjs")


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="needs hypothesis")
def test_any_kill_schedule_resumes_bitexact(tmp_path_factory):
    """Property: for ANY chunk length (ragged tail included) and ANY
    schedule of interruptions, chaining interrupted runs with resume=True
    reproduces the uninterrupted trajectory bit-for-bit."""

    @settings(max_examples=8, deadline=None, derandomize=True)
    @given(chunk=st.sampled_from([30, 50, 60, 80]),
           kills=st.lists(st.integers(min_value=1, max_value=3), min_size=1,
                          max_size=3))
    def prop(chunk, kills):
        _check_kill_schedule(chunk, kills, tmp_path_factory)

    prop()


def _check_kill_schedule(chunk, kills, tmp_path_factory):
    full = run_policy_streams(_BFJS_STREAMS, policy="bfjs", engine="scan",
                              **_BFJS_CFG)
    d = str(tmp_path_factory.mktemp("kills"))
    run_policy_streams(_BFJS_STREAMS, policy="bfjs", engine="scan",
                       checkpoint_dir=d, chunk=chunk,
                       stop_after_chunks=kills[0], **_BFJS_CFG)
    for k in kills[1:]:
        run_policy_streams(_BFJS_STREAMS, policy="bfjs", engine="scan",
                           checkpoint_dir=d, chunk=chunk, resume=True,
                           stop_after_chunks=k, **_BFJS_CFG)
    res = run_policy_streams(_BFJS_STREAMS, policy="bfjs", engine="scan",
                             checkpoint_dir=d, chunk=chunk, resume=True,
                             **_BFJS_CFG)
    _assert_bitmatch(res, full,
                     f"chunk={chunk} kills={kills}: resume diverged")


_CHILD = """
import os, signal, sys
import jax
import repro.core.engine.streaming as streaming
from repro.core.engine import make_streams, run_policy_streams

def sampler(key, n):
    return jax.random.uniform(key, (n,), minval=0.1, maxval=0.6)

streams = make_streams(jax.random.PRNGKey(3), 0.6, 0.5, sampler, L=4, K=3,
                       A_max=4, horizon=240, fault_rate=0.02,
                       repair_rate=0.3)
_real, _calls = streaming._save_step, 0

def _killing_save(*args, **kwargs):
    global _calls
    _real(*args, **kwargs)
    _calls += 1
    if _calls >= 2:
        os.kill(os.getpid(), signal.SIGKILL)

streaming._save_step = _killing_save
run_policy_streams(streams, policy="bfjs", engine="scan",
                   checkpoint_dir=sys.argv[1], chunk=60, L=4, K=3, Qcap=32,
                   A_max=4)
sys.exit("survived past the kill point")
"""


def test_sigkill_mid_sweep_then_resume(tmp_path):
    """A real SIGKILL delivered inside the checkpoint writer (no cleanup,
    no atexit): the surviving checkpoints resume to the exact
    straight-through trajectory."""
    streams, cfg = _BFJS_STREAMS, _BFJS_CFG
    full = run_policy_streams(streams, policy="bfjs", engine="scan", **cfg)
    d = str(tmp_path)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep \
        + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _CHILD, d], env=env,
                          capture_output=True, text=True)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                                proc.stderr[-2000:])
    assert ckpt.latest_step(d) == 2          # died right after save #2
    res = run_policy_streams(streams, policy="bfjs", engine="scan",
                             checkpoint_dir=d, chunk=60, resume=True, **cfg)
    _assert_bitmatch(res, full, "post-SIGKILL resume diverged")


# ---------------------------------------------------------------------------
# resume validation: never continue a different sweep
# ---------------------------------------------------------------------------
def test_resume_refuses_mismatched_sweep(tmp_path):
    streams, cfg = _BFJS_STREAMS, _BFJS_CFG
    d = str(tmp_path)
    run_policy_streams(streams, policy="bfjs", engine="scan",
                       checkpoint_dir=d, chunk=60, stop_after_chunks=1,
                       **cfg)
    with pytest.raises(ValueError, match="different sweep"):
        run_policy_streams(streams, policy="bfjs", engine="scan",
                           checkpoint_dir=d, chunk=80, resume=True, **cfg)
    other = streams._replace(sizes=streams.sizes * 0.5)
    with pytest.raises(ValueError, match="different sweep"):
        run_policy_streams(other, policy="bfjs", engine="scan",
                           checkpoint_dir=d, chunk=60, resume=True, **cfg)
    assert streaming.streams_fingerprint(other) \
        != streaming.streams_fingerprint(streams)
    # dropping the fault plane is a different sweep too
    with pytest.raises(ValueError, match="different sweep"):
        run_policy_streams(streams._replace(up=None), policy="bfjs",
                           engine="scan", checkpoint_dir=d, chunk=60,
                           resume=True, **cfg)


def test_resume_refuses_a_stream_changed_after_its_first_chunk(tmp_path):
    """The manifest pins every stream plane, not only the first chunk's:
    a sweep whose streams differ only in the chunks not yet run is a
    different sweep too."""
    streams, cfg = _BFJS_STREAMS, _BFJS_CFG
    d = str(tmp_path)
    run_policy_streams(streams, policy="bfjs", engine="scan",
                       checkpoint_dir=d, chunk=60, stop_after_chunks=1,
                       **cfg)
    late = streams._replace(durs=streams.durs.at[-1].add(1))
    with pytest.raises(ValueError, match="different sweep"):
        run_policy_streams(late, policy="bfjs", engine="scan",
                           checkpoint_dir=d, chunk=60, resume=True, **cfg)


def test_chunked_rejects_bad_usage(tmp_path):
    streams, cfg = _BFJS_STREAMS, _BFJS_CFG
    with pytest.raises(ValueError, match='engine="scan"'):
        run_policy_streams(streams, policy="bfjs", engine="pallas",
                           chunk=60, **cfg)
    with pytest.raises(ValueError, match="chunk"):
        run_policy_streams(streams, policy="bfjs", engine="scan",
                           checkpoint_dir=str(tmp_path), **cfg)
    with pytest.raises(ValueError, match="chunk must be positive"):
        run_policy_streams(streams, policy="bfjs", engine="scan", chunk=0,
                           **cfg)
    with pytest.raises(ValueError, match="resume=True needs"):
        stream_policy(iter_stream_chunks(streams, 60), policy="bfjs",
                      resume=True, **cfg)
    with pytest.raises(ValueError, match="no stateful scan engine"):
        stream_policy(iter_stream_chunks(streams, 60), policy="nope", **cfg)
    with pytest.raises(ValueError, match="nothing to run"):
        run_policy_streams(streams, policy="bfjs", engine="scan", chunk=60,
                           stop_after_chunks=0, **cfg)


# ---------------------------------------------------------------------------
# satellite: checkpoint round-trips of engine-carry dtypes
# ---------------------------------------------------------------------------
def test_ckpt_round_trips_engine_carry_bitexact(case, tmp_path):
    """The full scan carry (int32 grids, float32 planes, the bool
    ``up_last`` lane) and the partial PolicyResult survive
    ``ckpt.save``/``_load_step`` with dtype and bits intact."""
    policy, streams, cfg, full = case
    if policy == "bfjs-mr":
        res, state = run_bfjs_mr_streams(streams, capacity=(1.0, 1.0),
                                         return_state=True, **cfg)
    else:
        from repro.core.engine.bfjs import run_bfjs_streams
        from repro.core.engine.vqs import run_vqs_streams
        runner = run_vqs_streams if policy == "vqs" else run_bfjs_streams
        res, state = runner(streams, return_state=True, **cfg)
    dtypes = {np.dtype(a.dtype) for a in state}
    assert {np.dtype(np.int32), np.dtype(bool)} <= dtypes, dtypes
    ckpt.save(str(tmp_path), 1, {"state": state, "partial": res})
    state2, res2 = streaming._load_step(str(tmp_path), 1)
    assert len(state2) == len(state)
    for i, (a, b) in enumerate(zip(state, state2)):
        assert a.dtype == b.dtype and a.shape == b.shape, (policy, i)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{policy}: carry leaf {i}")
    _assert_bitmatch(res2, res, f"{policy}: PolicyResult round-trip")


def test_ckpt_round_trips_T_R_occupancy_plane(tmp_path):
    """The (T, R) float32 occupancy plane of a multi-resource result —
    restore via ``like`` pytree is bit-exact, dtype preserved."""
    streams, cfg = _case("bfjs-mr")
    res = run_policy_streams(streams, policy="bfjs-mr", engine="scan",
                             **cfg)
    assert res.occupancy.shape == (T, 2)
    assert res.occupancy.dtype == jnp.float32
    ckpt.save(str(tmp_path), 7, res)
    like = jax.tree.map(jnp.zeros_like, res)
    back = ckpt.restore(str(tmp_path), 7, like)
    _assert_bitmatch(back, res, "(T, R) occupancy round-trip")


def test_ckpt_atomicity_layout(tmp_path):
    """tmp-then-rename: a completed save leaves no tmp droppings, and the
    step directory holds the npz + manifest pair."""
    ckpt.save(str(tmp_path), 3, {"x": jnp.arange(4, dtype=jnp.int32)})
    names = sorted(os.listdir(tmp_path))
    assert names == ["step_00000003"]
    inner = sorted(os.listdir(tmp_path / "step_00000003"))
    assert inner == ["arrays.npz", "manifest.json"]
