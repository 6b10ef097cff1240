"""Test set-up for the benchmark's own tests: the CPU, no tuning cache,
and the checkout root and ``src`` importable (``tests/conftest.py`` does
not apply outside ``tests/``)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("REPRO_TUNING_CACHE", "off")

import argparse  # noqa: E402

import pytest  # noqa: E402

#: Sizes at which a test run on the CPU can hold each cell; the load stays
#: near the configuration's (queues form, nothing is dropped).
TINY_SIZES = dict(L=16, Qcap=128, A_max=8, lam_per_server=0.072, mu=0.04)
SWEEP = dict(config="synthetic-1000", metric="sweep_slots_per_s")
REPLAY = dict(config="synthetic-1000", metric="replay_slots_per_s")
TINY = {
    "synthetic-1000.vqsbf-kernel": dict(
        SWEEP, params=dict(horizon=80, work_steps=12,
                           program=dict(window=40, strict=True))),
    "synthetic-1000.bfjs-scan": dict(
        SWEEP, params=dict(horizon=160, G=4, work_steps=24)),
    "synthetic-1000.vqsbf-scan": dict(
        SWEEP, params=dict(horizon=160, G=4, work_steps=24)),
    "synthetic-1000.replay-vqsbf": dict(
        REPLAY, params=dict(chunk_slots=64, warmup_chunks=3,
                            trace_slots=64 * 200, trace_rows=64,
                            work_steps=24)),
}


def tiny_cell(name: str, **params):
    """Cell ``name`` at its test size, built from its traffic and
    configuration files, with ``params`` overriding its traffic file (e.g.
    a lower ``work_steps``)."""
    from bench import harness
    tiny = TINY[name]
    traffic = name.split(".", 1)[1]
    traffic_file = harness.load_json(
        os.path.join(harness.BENCH, "traffic", f"{traffic}.json"))
    config = harness.load_json(
        os.path.join(harness.BENCH, "configs", f"{tiny['config']}.json"))
    config["sizes"].update(TINY_SIZES)
    metrics = [{"name": tiny["metric"], "unit": "slots/s"},
               {"name": "setup_s", "unit": "s"}]
    return harness.Cell(name, {"chips": 1, "traffic": traffic},
                        {**traffic_file, **tiny["params"], **params}, config,
                        metrics, [])


@pytest.fixture
def tiny_run():
    """Run a cell at its test size on the CPU through the harness (the
    look for a chip is skipped); returns the result line and checks."""
    def run(name: str, seed: int = 2**31 + 11, seconds: float = 0.5,
            cell=None, **params):
        import jax
        from bench import run as bench_run
        args = argparse.Namespace(workload=name, seed=seed,
                                  seconds=seconds, trace=0)
        return bench_run.run_cell(args, cell or tiny_cell(name, **params),
                                  jax.devices())
    return run
