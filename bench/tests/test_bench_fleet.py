"""The fleet cell at test size on the CPU: correct on sound runs, not
correct under its control (a work bound too low to finish a slot) or any
planted fault of the sweeps (``test_bench_faults``), and its generator
lays out the configured machine mix."""
import argparse
import os

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests.test_bench_faults import sweep_fault
import repro.core.engine as engine_api

NAME = "google2011-fleet-1000.bfjsmr-kernel"
#: 12 machines of six of the configuration's classes, the 0.50/0.03 one
#: (which fits no job) among them, loaded so that a queue forms.
TINY_SIZES = dict(L=12, machines=[5, 3, 1, 1, 1, 1, 0, 0, 0, 0], Qcap=128,
                  A_max=8, lam=0.6, mu=0.04)


def tiny_cell(**params):
    traffic = harness.load_json(os.path.join(
        harness.BENCH, "traffic", "fleet-bfjsmr-kernel.json"))
    config = harness.load_json(os.path.join(
        harness.BENCH, "configs", "google2011-fleet-1000.json"))
    config["sizes"].update(TINY_SIZES)
    metrics = [{"name": "sweep_slots_per_s", "unit": "slots/s"},
               {"name": "setup_s", "unit": "s"}]
    return harness.Cell(NAME, {"chips": 1, "traffic": "fleet-bfjsmr-kernel"},
                        {**traffic, "horizon": 80, "work_steps": 24,
                         **params}, config, metrics, [])


def run(**params):
    from bench import run as bench_run
    args = argparse.Namespace(workload=NAME, seed=2**31 + 17, seconds=0.5,
                              trace=0)
    return bench_run.run_cell(args, tiny_cell(**params), jax.devices())


def test_fleet_cell_is_correct():
    out = run()
    line, diag = out["line"], out["diag"]
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"sweep_slots_per_s", "setup_s"}
    assert diag["compiles_in_window"] == 0
    assert diag["queue_max"] > 0 and diag["bfs_placements"] > 0
    assert diag["bfs_placements_match"]
    assert diag["steps_per_member_slot"] >= 1


def test_fleet_control_is_not_correct():
    line = run(work_steps=1)["line"]
    assert not line["correct"]
    assert line["checks"]["truncated"]["value"] > 0
    assert line["checks"]["mismatched_slots"]["value"] > 0


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
def test_broken_fleet_sweep_is_not_correct(monkeypatch, kind):
    monkeypatch.setattr(engine_api, "monte_carlo_policy", sweep_fault(kind))
    line = run()["line"]
    assert not line["correct"]
    assert line["checks"]["mismatched_slots"]["value"] > 0


def test_fleet_generator_lays_out_the_machine_mix():
    cell = harness.find_cell(NAME)
    sizes = cell.config["sizes"]
    caps = harness.generator_module(cell).capacities(sizes)
    assert caps.shape == (1000, 2)
    assert caps.sum(axis=0) == pytest.approx([529.0, 470.26])
    rows, counts = np.unique(caps, axis=0, return_counts=True)
    want = {tuple(c): m for c, m in zip(sizes["classes"], sizes["machines"])
            if m}
    assert dict(zip(map(tuple, rows.tolist()), counts.tolist())) == want
    # the layout is shuffled: the first 535 rows are not all one class
    assert len(np.unique(caps[:535], axis=0)) > 1
    # the allocation is Table 1's by largest remainder
    table = cell.config["table1"]["machines"]
    assert sum(sizes["machines"]) == sizes["L"]
    assert all(abs(m - t * sizes["L"] / sum(table)) < 1
               for m, t in zip(sizes["machines"], table))
