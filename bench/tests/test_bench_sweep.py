"""The sweep path at test size on the CPU: correct on sound runs,
and its control (a work bound too low to finish a slot, which breaks the
no-truncation guarantee) comes out not correct: the program counts the
truncated slots, and the reference, which runs every slot to its end,
parts from its trajectory."""
import pytest

SWEEP_CELLS = ["synthetic-1000.vqsbf-kernel", "synthetic-1000.bfjs-scan",
               "synthetic-1000.vqsbf-scan"]


@pytest.mark.parametrize("name", SWEEP_CELLS)
def test_sweep_cell_is_correct(tiny_run, name):
    out = tiny_run(name)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"sweep_slots_per_s", "setup_s"}
    assert line["metrics"]["sweep_slots_per_s"]["value"] > 0
    assert out["diag"]["compiles_in_window"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("name", SWEEP_CELLS)
def test_sweep_control_is_not_correct(tiny_run, name):
    line = tiny_run(name, work_steps=1)["line"]
    assert not line["correct"]
    assert line["checks"]["truncated"]["value"] > 0
    assert line["checks"]["mismatched_slots"]["value"] > 0
