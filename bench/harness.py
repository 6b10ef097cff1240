"""The benchmark's data-driven core: it finds a cell's files by name and
runs the cell.

Everything that belongs to one cell, configuration, path or per-layer
metric sits in a file of its own under ``bench/``:

  * ``BENCHMARK.json`` (checkout root): the cells, their configuration and
    traffic names, and the metrics each reports;
  * ``bench/configs/<config>.json``: one deployment (sizes, source,
    guarantees, and the ``generator`` of its traffic);
  * ``bench/traffic/<traffic>.json``: one traffic mix, the data a cell
    runs: its path, policy and engine, the program's options, and the mix
    parameters (ensemble size, horizon, chunking, warm-up);
  * ``bench/traffic/<generator>.py``: a general traffic generator that
    configurations name;
  * ``bench/reference/<policy>.py``: the plain reference of one policy
    (dashes in the policy's name become underscores);
  * ``bench/paths/<path>.py``: the runner of one entry point, a
    ``run(ctx)`` function;
  * ``bench/metrics/<metric>.py``: the reader of one per-layer metric, a
    ``read(run)`` function that returns a number or None.

Adding any of them is adding a file; nothing here names a cell.
"""
from __future__ import annotations

import importlib.util
import json
import os
import time
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclass(frozen=True)
class Check:
    """One number compared to decide ``correct``: it passes when
    ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (names may hold dots and dashes)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """A cell of ``BENCHMARK.json`` with its files loaded."""
    name: str
    entry: dict
    params: dict
    config: dict
    end_to_end: list
    per_layer: list
    bench_dir: str = BENCH

    @property
    def path(self) -> str:
        return self.params["path"]


def _applies(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(name: str, bench_dir: str = BENCH,
              spec_path: str | None = None) -> Cell:
    """Load cell ``name``: its entry in ``BENCHMARK.json``, its cell file,
    its configuration, and the metrics it reports."""
    spec = load_json(spec_path or os.path.join(os.path.dirname(bench_dir),
                                               "BENCHMARK.json"))
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json; cells: "
                       f"{', '.join(sorted(entries))}")
    entry = entries[name]
    params = load_json(os.path.join(bench_dir, "traffic",
                                    f"{entry['traffic']}.json"))
    confs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(os.path.dirname(bench_dir),
                                    confs[entry["config"]]["file"]))
    e2e = [m for m in spec["end_to_end"] if "workloads" not in m
           or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _applies(m, name, e2e_names)]
    return Cell(name, entry, params, config, e2e, layer, bench_dir)


def path_module(cell: Cell):
    return load_module(os.path.join(cell.bench_dir, "paths",
                                    f"{cell.path}.py"),
                       f"bench_path_{cell.path}")


def generator_module(cell: Cell):
    """The traffic generator the cell's configuration names."""
    name = cell.config["generator"]
    return load_module(os.path.join(cell.bench_dir, "traffic", f"{name}.py"),
                       f"bench_traffic_{name}")


def reference_module(policy: str, bench_dir: str = BENCH):
    """The plain reference of ``policy``; a policy with none is an error."""
    name = policy.replace("-", "_")
    path = os.path.join(bench_dir, "reference", f"{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no reference for policy {policy!r} ({path})")
    return load_module(path, f"bench_reference_{name}")


def metric_reader(name: str, bench_dir: str = BENCH):
    return load_module(os.path.join(bench_dir, "metrics", f"{name}.py"),
                       "bench_metric_" + name.replace(".", "_"))


def device_peaks(kind: str, bench_dir: str = BENCH) -> dict:
    """The published peaks of ``kind``; a device not in the table is an
    error, never a default."""
    table = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {', '.join(table)})")
    return table[kind]


class Window:
    """The measured window: host-clock bounds and, when tracing, the
    profiler with a ``bench.window`` host span around the traced part: the
    first ``trace_seconds`` of the window (rounded up to the path's next
    ``poll``), or all of it."""

    def __init__(self, trace_dir: str | None, compiles: "CompileCounter",
                 trace_seconds: float | None = None):
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.compiles = compiles
        self.t_open = self.t_close = None
        self.tracing = False
        self.stop_trace_s = None
        self._span = None

    def open(self) -> None:
        import jax
        if self.trace_dir is not None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.tracing = True
        self.compiles.mark()
        self.t_open = time.perf_counter()

    def poll(self) -> None:
        """Stop tracing once ``trace_seconds`` of the window have run."""
        if self.tracing and self.trace_seconds is not None and \
                time.perf_counter() - self.t_open >= self.trace_seconds:
            self._stop_trace()

    def close(self) -> None:
        self.t_close = time.perf_counter()
        self.compiles.stop()
        if self.tracing:
            self._stop_trace()

    def _stop_trace(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_trace_s = time.perf_counter() - t0
        self.tracing = False

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


class CompileCounter:
    """Counts backend compilations and traces between ``mark`` and
    ``stop`` (there should be none inside the window)."""

    def __init__(self):
        import jax
        self.active = False
        self.compiles = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if not self.active:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def mark(self) -> None:
        self.active = True

    def stop(self) -> None:
        self.active = False


@dataclass
class Context:
    """What a path's ``run(ctx)`` is given, and what it reports back
    besides its return value."""
    cell: dict
    config: dict
    seed: int
    seconds: float
    platform: str
    run_dir: str
    window: Window
    devices: list
    traffic: object = None
    reference: object = None
    diag: dict = field(default_factory=dict)
    memory_peak_bytes: int | None = None

    def note(self, **kv) -> None:
        """Diagnostics for the earlier output line (not metrics)."""
        self.diag.update(kv)

    def read_memory(self) -> None:
        """The peak device memory of the fullest chip, read once the
        window has closed and before the reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None
