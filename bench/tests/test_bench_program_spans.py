"""The program's ``repro.*`` spans read from a profiler trace
(``bench/program_spans.py``) and the six replay metrics that read them,
on one hand-written replay period (``data/replay_trace.pbtxt``, its
numbers in its header) and on the sweep trace, which has no such span."""
import os
from types import SimpleNamespace

import pytest

from bench import harness, program_spans, trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data")
REPLAY = os.path.join(DATA, "replay_trace.pbtxt")
SWEEP = os.path.join(DATA, "sweep_trace.pbtxt")

#: each reader's value on the replay period (ms per chunk, or %)
EXPECTED = {
    "rebucket_ms_per_chunk.replay": 0.003,     # ingest [11, 14], chunk 3
    "stage_ms_per_chunk.replay": 0.004,        # stage [15, 19], chunk 3
    # drain 2 us (clipped) + 1 us over chunks 0 and 1, + ckpt wait 30 us
    "wait_ms_per_chunk.replay": 0.0015 + 0.030,
    "ckpt_fetch_ms_per_chunk.replay": 0.035,   # [50, 85]
    "ckpt_write_ms_per_chunk.replay": 0.010,   # [85, 95]
    # inside [20, 95] the device is busy until 48: 47 us of the 100 us
    "checkpoint_idle_pct.replay": 47.0,
}


def profile(path):
    from jax.profiler import ProfileData
    with open(path) as f:
        return ProfileData.from_text_proto(f.read())


@pytest.fixture
def traced_run(tmp_path, monkeypatch):
    """A run in progress whose trace is ``path``, laid out as
    ``bench/run.py`` lays out a run's directory."""
    from jax.profiler import ProfileData

    def make(path):
        with open(path) as f:
            text = f.read()
        trace = tmp_path / f"synthetic-1000.replay-vqsbf.{os.getpid()}" / \
            "trace" / "plugins" / "profile" / "run"
        trace.mkdir(parents=True)
        (trace / "host.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(text))
        monkeypatch.setattr(program_spans, "RUNS_DIR", str(tmp_path))
        return SimpleNamespace(
            reduction=trace_reduce.reduce_profile(profile(path)))
    return make


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reader_reads_the_replay_period(traced_run, metric):
    run = traced_run(REPLAY)
    value = harness.metric_reader(metric).read(run)
    assert value == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reader_is_silent_without_program_spans(traced_run, metric):
    assert harness.metric_reader(metric).read(traced_run(SWEEP)) is None


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_each_reader_is_silent_on_an_untraced_run(metric):
    run = SimpleNamespace(reduction=None)
    assert harness.metric_reader(metric).read(run) is None


def test_every_new_metric_is_in_the_benchmark():
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    layer = {m["name"]: m for m in spec["per_layer"]}
    for name in EXPECTED:
        assert layer[name]["workloads"] == ["synthetic-1000.replay-vqsbf"]
        assert layer[name]["moves"] == "replay_slots_per_s"


@pytest.fixture(scope="module")
def spans():
    return program_spans.collect(profile(REPLAY))


def test_spans_are_clipped_to_the_window(spans):
    waits = sorted((s.start, s.end) for s in spans.spans
                   if s.name == "repro.stream.wait")
    assert waits == [(10_000, 12_000), (96_000, 97_000)]
    # the re-bucketing of chunk 4 starts after the window: not counted
    assert {s.chunk for s in spans.spans
            if s.name == "repro.ingest.chunk"} == {3}


def test_a_span_without_a_chunk_is_not_counted(spans):
    stages = [s for s in spans.spans if s.name == "repro.stream.stage"]
    assert [(s.start, s.end, s.chunk) for s in stages] == \
        [(15_000, 19_000, 3)]


def test_spans_on_worker_threads_are_collected(spans):
    ingest, = [s for s in spans.spans if s.name == "repro.ingest.chunk"]
    assert ingest.line == "/host:CPU/supervised-chunk ingestion"


def test_checkpoint_idle_is_part_of_the_device_idle(spans):
    whole = trace_reduce.reduce_profile(profile(REPLAY)).idle_pct
    assert whole == pytest.approx(51.0)
    assert spans.idle_pct_under("repro.stream.checkpoint") <= whole
    # the copy runs with the device idle throughout
    assert spans.idle_pct_under("repro.ckpt.fetch") == pytest.approx(35.0)
    assert spans.idle_pct_under("repro.no.such.span") is None


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    with open(REPLAY) as f:
        text = f.read().replace('"bench.window"', '"other"')
    with pytest.raises(ValueError, match="bench.window"):
        program_spans.collect(ProfileData.from_text_proto(text))
