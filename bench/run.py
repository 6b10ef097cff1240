"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its path and its per-layer metrics are found
by name (``bench/harness.py``).  The run exits nonzero, before any work,
when JAX finds no TPU or fewer chips than the cell asks for; it never falls
back to the CPU.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, last, ``checks``
(each number compared with its limit).  With ``--trace 1`` the line also
holds ``breakdown`` and ``device`` holds ``busy_s`` and ``window_s``.  An
earlier line holds diagnostics (dropped and truncated counts, backpressure
counters, peak memory, compilations inside the window).  The checks are
also the last lines on standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

RUNS_DIR = os.path.join(ROOT, ".bench_runs")
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class Run:
    """What a per-layer metric reader is given."""

    def __init__(self, cell, config, work, reduction, peaks, reference):
        self.cell, self.config, self.work = cell, config, work
        self.reduction, self.peaks = reduction, peaks
        self.reference = reference


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def run_cell(args, cell, devices) -> dict:
    """Run ``cell`` on ``devices``; returns the result line's object, the
    diagnostics and the checks."""
    import jax
    from bench import harness, trace_reduce
    from repro.compile_cache import use_compile_cache
    from repro.kernels.common import GracefulDegradationWarning

    kind = devices[0].device_kind
    os.environ["REPRO_TUNING_CACHE"] = "off"
    warnings.simplefilter("error", GracefulDegradationWarning)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    run_dir = os.path.join(RUNS_DIR, f"{cell.name}.{os.getpid()}")
    trace_dir = os.path.join(run_dir, "trace") if args.trace else None
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    compiles = harness.CompileCounter()
    window = harness.Window(trace_dir, compiles,
                            cell.params.get("trace_seconds"))
    reference = harness.reference_module(cell.params["policy"],
                                         cell.bench_dir)
    ctx = harness.Context(
        cell=cell.params, config=cell.config, seed=args.seed,
        seconds=args.seconds, platform=devices[0].platform,
        run_dir=run_dir, window=window, devices=devices,
        traffic=harness.generator_module(cell), reference=reference)
    out = harness.path_module(cell).run(ctx)
    setup_s = window.t_open - T_START

    metrics = {}
    if args.trace:
        t0 = time.perf_counter()
        reduction = trace_reduce.reduce_dir(trace_dir)
        ctx.note(stop_trace_s=window.stop_trace_s,
                 reduce_trace_s=time.perf_counter() - t0)
        run = Run(cell.params, cell.config, out["work"], reduction,
                  harness.device_peaks(kind), reference)
        for m in cell.per_layer:
            value = harness.metric_reader(m["name"], cell.bench_dir
                                          ).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        reduction = None
        values = dict(out["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    shutil.rmtree(run_dir, ignore_errors=True)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    line = {"correct": all(c.ok for c in out["checks"]),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device}
    if reduction is not None:
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        line["breakdown"] = reduction.breakdown()
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out["checks"]}
    diag = dict(ctx.diag, setup_s=setup_s, window_s=window.seconds,
                compiles_in_window=compiles.compiles,
                traces_in_window=compiles.traces,
                peak_bytes_in_use=ctx.memory_peak_bytes)
    return {"line": line, "diag": diag, "checks": out["checks"]}


def main(argv=None) -> int:
    args = parse(argv)
    import jax
    from bench import harness

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r});"
              " this benchmark runs on the chip only", file=sys.stderr)
        return 2
    harness.device_peaks(devices[0].device_kind)
    cell = harness.find_cell(args.workload)
    chips = cell.entry["chips"]
    if len(devices) < chips:
        print(f"bench: cell {cell.name} needs {chips} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    result = run_cell(args, cell, devices[:chips])
    print("diagnostics: " + json.dumps(result["diag"], default=str),
          flush=True)
    print(json.dumps(result["line"]), flush=True)
    for c in result["checks"]:
        print(f"check {c.name}: {c.value} (limit {c.limit})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
