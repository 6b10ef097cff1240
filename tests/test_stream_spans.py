"""The streaming pipeline's profiler spans (``repro.*``): a supervised,
checkpointed ``stream_policy`` over a re-bucketed trace, traced by the
profiler on the CPU.

Each executed chunk has one ingestion, one staging and one checkpoint
span carrying its index as the ``chunk`` stat; the checkpoint writer's
wait, fetch and write nest inside their checkpoint span on the same
thread; the fetch's ``bytes`` stat is the manifest's ``total_bytes``; and
tracing leaves the result bit-identical.
"""
import glob
import os
import warnings
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.core import trace as trace_mod
from repro.core.engine import (Supervisor, stream_chunks_from_trace,
                               stream_policy)

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "google_like_50.csv")
CFG = dict(L=4, K=5, Qcap=48, J=3)
TRAJ = ("queue_len", "occupancy", "departed", "dropped", "truncated")
CKPT_SPANS = ("repro.ckpt.wait", "repro.ckpt.fetch", "repro.ckpt.write")


def _replay(ckpt_dir):
    cc, mc = trace_mod.scan_trace_maxima(FIXTURE)
    rows = trace_mod.iter_trace_csv(FIXTURE, chunk_rows=13,
                                    slot_seconds=10.0, cpu_capacity=cc,
                                    mem_capacity=mc)
    # both timeouts set: ingestion, staging and the drain run on the
    # supervisor's worker threads
    sup = Supervisor(compute_timeout=120.0, stage_timeout=120.0,
                     sleep=lambda s: None)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return stream_policy(
            stream_chunks_from_trace(rows, chunk_slots=6, A_max=12),
            policy="vqs-bf", supervisor=sup, checkpoint_dir=str(ckpt_dir),
            **CFG)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from jax.profiler import ProfileData
    tmp = tmp_path_factory.mktemp("spans")
    plain = _replay(tmp / "plain")           # also compiles
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp / "trace"), profiler_options=opts):
        res = _replay(tmp / "traced")
    trace, = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                       recursive=True)
    spans = defaultdict(list)    # name -> [(start, end, line, stats)]
    for plane in ProfileData.from_file(trace).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    spans[ev.name].append((ev.start_ns, ev.end_ns,
                                           (plane.name, n), dict(ev.stats)))
    return plain, res, spans, str(tmp / "traced")


def test_one_span_per_chunk_and_phase(traced):
    _, _, spans, ckpt_dir = traced
    executed = ckpt.latest_step(ckpt_dir)
    assert executed >= 4
    for name in ("repro.ingest.chunk", "repro.stream.stage",
                 "repro.stream.checkpoint") + CKPT_SPANS:
        chunks = [s[3].get("chunk") for s in spans[name]]
        for c in range(executed):
            assert chunks.count(c) == 1, (name, c, chunks)
    # the drain waits on each chunk at most once
    waited = [s[3]["chunk"] for s in spans["repro.stream.wait"]]
    assert waited and len(set(waited)) == len(waited)
    assert set(waited) <= set(range(executed))


def test_checkpoint_phases_nest_on_the_same_thread(traced):
    _, _, spans, _ = traced
    outer = {s[3]["chunk"]: s for s in spans["repro.stream.checkpoint"]}
    for name in CKPT_SPANS:
        for start, end, line, stats in spans[name]:
            o_start, o_end, o_line, _ = outer[stats["chunk"]]
            assert o_start <= start <= end <= o_end, name
            assert line == o_line, name


def test_fetch_bytes_are_the_manifest_total(traced):
    _, _, spans, ckpt_dir = traced
    for _, _, _, stats in spans["repro.ckpt.fetch"]:
        manifest = ckpt.read_manifest(ckpt_dir, stats["chunk"] + 1)
        assert stats["bytes"] == manifest["total_bytes"] > 0
        assert stats["leaves"] == manifest["num_arrays"]
    for _, _, _, stats in spans["repro.ckpt.write"]:
        npz = os.path.join(ckpt_dir, f"step_{stats['chunk'] + 1:08d}",
                           "arrays.npz")
        assert stats["bytes"] == os.path.getsize(npz)


def test_tracing_leaves_the_result_bit_identical(traced):
    plain, res, _, _ = traced
    for f in TRAJ:
        np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                      np.asarray(getattr(res, f)),
                                      err_msg=f)


def test_save_without_a_chunk_has_no_chunk_stat(tmp_path):
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "trace"),
                            profiler_options=opts):
        ckpt.save(str(tmp_path / "ck"), 1, {"x": np.arange(5)})
    trace, = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                       recursive=True)
    seen = {ev.name: dict(ev.stats)
            for plane in ProfileData.from_file(trace).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.ckpt.")}
    assert set(seen) == set(CKPT_SPANS)
    assert all("chunk" not in stats for stats in seen.values())
    assert seen["repro.ckpt.fetch"]["bytes"] == np.arange(5).nbytes
