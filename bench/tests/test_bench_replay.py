"""The replay path at test size on the CPU: correct on sound runs
(every chunk, the returned tail and a resumed chunk match the reference),
and its control (a work bound too low to finish a slot) is not correct."""

REPLAY = "synthetic-1000.replay-vqsbf"


def test_replay_cell_is_correct(tiny_run):
    out = tiny_run(REPLAY)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"replay_slots_per_s", "setup_s"}
    assert out["diag"]["compiles_in_window"] == 0
    assert out["diag"]["quarantined"] == 0


def test_replay_control_is_not_correct(tiny_run):
    line = tiny_run(REPLAY, work_steps=1)["line"]
    assert not line["correct"]
    assert line["checks"]["truncated"]["value"] > 0
    assert line["checks"]["mismatched_slots"]["value"] > 0


def test_replay_without_checkpoints_compares_the_tail(tiny_run):
    out = tiny_run(REPLAY, checkpoint=False)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["attempted"] == 1


def test_a_trace_that_runs_out_is_an_error(tiny_run):
    import pytest
    with pytest.raises(RuntimeError, match="trace ran out"):
        tiny_run(REPLAY, trace_slots=64 * 4, seconds=30.0)
