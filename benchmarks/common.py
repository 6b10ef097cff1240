"""Shared benchmark plumbing: CSV rows `name,us_per_call,derived` plus a
machine-readable record registry dumped to BENCH_sched.json by run.py."""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

FULL = os.environ.get("REPRO_BENCH_FULL", "0") == "1"
SMOKE = os.environ.get("REPRO_BENCH_SMOKE", "0") == "1"

#: every row() call lands here as {"name", "us", "meta"}; run.py (or any
#: caller) serializes it with write_json() so perf is tracked across PRs.
RECORDS: list[dict] = []


def row(name: str, us_per_call: float, derived: str = "") -> str:
    line = f"{name},{us_per_call:.2f},{derived}"
    print(line, flush=True)
    RECORDS.append({"name": name, "us": round(us_per_call, 3),
                    "meta": derived})
    return line


def timed(fn, *args, repeat: int = 1, **kw):
    t0 = time.time()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.time() - t0) / repeat
    return out, dt * 1e6


def timed_best(fn, *args, repeat: int = 5, **kw):
    """Best-of-N wall clock in microseconds (noise-robust micro timing).
    The first (compile) call is excluded from the measurement."""
    fn(*args, **kw)
    best = float("inf")
    out = None
    for _ in range(1 if SMOKE else repeat):
        t0 = time.time()
        out = fn(*args, **kw)
        best = min(best, time.time() - t0)
    return out, best * 1e6


def timed_interleaved(variants: dict, rounds: int = 7) -> dict:
    """Round-robin best-of-N over named thunks, in microseconds.

    Engine comparisons must be timed INTERLEAVED so machine-load drift hits
    every variant equally — on shared hosts the wall clock of a single
    variant can swing +-50% between back-to-back runs, which would make a
    sequential comparison meaningless.  Each thunk runs once for warm-up /
    compile (excluded), then ``rounds`` timed passes (2 under SMOKE)."""
    for fn in variants.values():
        fn()
    best = {name: float("inf") for name in variants}
    for _ in range(2 if SMOKE else rounds):
        for name, fn in variants.items():
            t0 = time.time()
            fn()
            best[name] = min(best[name], time.time() - t0)
    return {name: b * 1e6 for name, b in best.items()}


def write_json(path: str) -> str:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump({"schema": "bench.v1", "benchmarks": RECORDS}, f, indent=1)
        f.write("\n")
    print(f"wrote {len(RECORDS)} records -> {path}", flush=True)
    return path
