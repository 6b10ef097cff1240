"""A cell, a configuration, a traffic mix, a traffic generator and a
per-layer metric added as files alone are found by name, and the new cell
runs; every cell of ``BENCHMARK.json`` has its files."""
import argparse
import json
import os
import shutil

import pytest

from bench import harness

NEW_CELL = "tiny-cluster.new-mix"

#: A generator that no other file names: two job sizes, drawn with equal
#: odds, with the stream chain of ``poisson_uniform``.
TWO_POINT = """
import functools

import jax
import jax.numpy as jnp

from bench.traffic import poisson_uniform as pu

call_keys = pu.call_keys


@functools.lru_cache(maxsize=None)
def _sampler(lo, hi):
    return functools.partial(_two_point, lo=lo, hi=hi)


def _two_point(key, n, *, lo, hi):
    return jnp.where(jax.random.bernoulli(key, 0.5, (n,)), hi, lo)


def sampler(sizes):
    return _sampler(*map(float, sizes["size_values"]))


def streams(key, sizes, horizon):
    return pu.make_streams(key, lam=sizes["lam_per_server"] * sizes["L"],
                           mu=sizes["mu"], sampler=sampler(sizes),
                           L=sizes["L"], K=sizes["K"], A_max=sizes["A_max"],
                           horizon=horizon)
"""


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of the benchmark with one more cell, configuration and
    metric, added as new files and new entries only."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny-cluster", "source": "test",
                            "file": "bench/configs/tiny-cluster.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": NEW_CELL, "config": "tiny-cluster",
                              "traffic": "new-mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({
        "name": "queue_share.sweep", "unit": "%", "better": "lower",
        "source": "host_clock", "layer": "engine",
        "moves": "sweep_slots_per_s", "workloads": [NEW_CELL]})
    spec["end_to_end"][0]["workloads"].append(NEW_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (root / "bench/configs/tiny-cluster.json").write_text(json.dumps(
        {"name": "tiny-cluster", "generator": "two_point",
         "sizes": {"L": 8, "K": 16, "Qcap": 64, "A_max": 8, "J": 4,
                   "size_values": [0.3, 0.6], "mu": 0.05,
                   "lam_per_server": 0.09}}))
    (root / "bench/traffic/new-mix.json").write_text(json.dumps(
        {"path": "sweep", "policy": "vqs-bf", "engine": "scan",
         "program_sizes": ["L", "K", "Qcap", "A_max", "J"], "program": {},
         "G": 2, "horizon": 120, "work_steps": 24}))
    (root / "bench/traffic/two_point.py").write_text(TWO_POINT)
    (root / "bench/metrics/queue_share.sweep.py").write_text(
        "def read(run):\n    return 42.0\n")
    return root / "bench"


def test_new_files_are_found_by_name(bench_copy):
    cell = harness.find_cell(NEW_CELL, bench_dir=str(bench_copy))
    assert cell.config["sizes"]["size_values"] == [0.3, 0.6]
    assert cell.params["horizon"] == 120
    assert {m["name"] for m in cell.end_to_end} \
        == {"sweep_slots_per_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["queue_share.sweep"]
    reader = harness.metric_reader("queue_share.sweep",
                                   bench_dir=str(bench_copy))
    assert reader.read(None) == 42.0
    assert harness.path_module(cell).run
    assert harness.generator_module(cell).sampler(cell.config["sizes"])


def test_a_cell_added_as_files_runs_on_its_own_traffic(bench_copy):
    import jax
    import numpy as np
    from bench import run as bench_run
    cell = harness.find_cell(NEW_CELL, bench_dir=str(bench_copy))
    args = argparse.Namespace(workload=NEW_CELL, seed=2**33 + 5,
                              seconds=0.3, trace=0)
    out = bench_run.run_cell(args, cell, jax.devices())
    assert out["line"]["correct"], out["line"]["checks"]
    assert set(out["line"]["metrics"]) == {"sweep_slots_per_s", "setup_s"}
    # the streams are the new generator's: every size is one of its two
    gen = harness.generator_module(cell)
    _, sizes, _ = gen.streams(gen.call_keys(1, 1, 1)[0], cell.config["sizes"],
                              40)
    assert set(np.unique(np.asarray(sizes)).tolist()) \
        == {np.float32(0.3), np.float32(0.6)}


def test_an_unknown_cell_is_an_error(bench_copy):
    with pytest.raises(KeyError, match="no cell"):
        harness.find_cell("no-such.cell", bench_dir=str(bench_copy))


@pytest.mark.parametrize("name", [
    w["name"] for w in json.load(open(os.path.join(
        harness.ROOT, "BENCHMARK.json")))["workloads"]])
def test_every_cell_has_its_files(name):
    cell = harness.find_cell(name)
    assert harness.path_module(cell).run
    assert cell.end_to_end and cell.per_layer
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert harness.metric_reader(m["name"]).read


def test_run_refuses_without_a_tpu(capsys):
    from bench import run as bench_run
    rc = bench_run.main(["--workload", "synthetic-1000.vqsbf-kernel",
                         "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err
