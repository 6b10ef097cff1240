"""Shape-keyed kernel autotuner with a persistent JSON tuning cache.

The engines expose two launch knobs whose best values depend only on the
launch SHAPE, not on the realized randomness: ``work_steps`` (the bound of
the per-slot placement work list — ``micro/jax_bfjs_slot_tuned`` shows it
alone is worth ~2x) and ``window`` (the Pallas kernels' VMEM time-window
length).  This module sweeps those knobs per

    (policy, L, K, R, Qcap, A_max, engine, backend)

shape, verifies every candidate BIT-MATCHES the untuned run before it can
win (a faster-but-divergent config is rejected, never cached), and stores
winners in a persistent JSON cache so later runs pick tuned configs
automatically: ``run_policy`` / ``run_policy_streams`` /
``monte_carlo_policy`` / ``serving.estimate_capacity`` consult the cache
(:func:`apply_tuned`) whenever the caller did not pin the knob explicitly.

Cache contract (DESIGN.md §11):

  * location: ``REPRO_TUNING_CACHE`` env var > ``~/.cache/repro/
    sched_tuning.json``; the special value ``off`` disables both lookup
    and writes (the bypass the test suite runs under);
  * writes are atomic (tmp file + ``os.replace``, the same crash-safety
    rule as ``repro.checkpoint``), so a killed sweep never leaves a torn
    cache;
  * a corrupt or schema-mismatched cache file is IGNORED with a loud
    warning and overwritten by the next store — never a crash, never a
    silently-wrong config;
  * invalidation: entries are keyed by the full launch shape + backend and
    carry the module ``SCHEMA`` version; bumping ``SCHEMA`` (any PR that
    changes engine/kernel cost structure) discards every stale entry;
  * the autotuner is BYPASSED (no lookup, no sweep) for
    ``engine="reference"`` (nothing to tune) and refuses to *produce*
    entries for Pallas kernels running in interpret mode — interpret
    timings are correctness-grade, not perf-grade (pass
    ``allow_interpret=True`` to override, e.g. in tests).

A tuned ``work_steps`` is still only a bound: a different workload at the
same shape may need more steps, and then the engines' ``truncated``
counter reports the divergence loudly — the bit-match contract stays
enforced at run time, not assumed from the cache.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
import warnings

import numpy as np

#: Bumping this discards every previously-cached entry (see invalidation
#: rule above) — bump whenever an engine/kernel change shifts the cost
#: model under the same shape key.
SCHEMA = "tuning.v2"

_ENV = "REPRO_TUNING_CACHE"
_DEFAULT_PATH = os.path.join("~", ".cache", "repro", "sched_tuning.json")

#: Shape-key defaults, mirroring the policy runners' signature defaults so
#: a knob the caller leaves unset keys the same shape the runner will use.
_SHAPE_DEFAULTS = {"L": 8, "K": 16, "Qcap": 512, "A_max": 8}


def cache_path() -> str | None:
    """Resolved cache file path, or None when tuning is disabled."""
    raw = os.environ.get(_ENV, "")
    if raw.lower() == "off":
        return None
    return os.path.expanduser(raw or _DEFAULT_PATH)


def tuning_enabled() -> bool:
    return cache_path() is not None


def shape_key(policy: str, engine: str, *, L: int, K: int, R: int,
              Qcap: int, A_max: int, backend: str | None = None) -> str:
    """The cache key of one launch shape (stable, human-readable)."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    return (f"{policy}|{engine}|{backend}|L={L}|K={K}|R={R}|"
            f"Qcap={Qcap}|A_max={A_max}")


class TuningCache:
    """Persistent shape-key -> winner-config map (atomic JSON writes)."""

    def __init__(self, path: str | None = None):
        self.path = cache_path() if path is None else os.path.expanduser(path)

    def load(self) -> dict:
        """All valid entries; corrupt/stale files are ignored loudly."""
        if self.path is None or not os.path.exists(self.path):
            return {}
        try:
            with open(self.path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError, UnicodeDecodeError) as e:
            warnings.warn(
                f"ignoring corrupt tuning cache at {self.path!r} ({e}); "
                "it will be overwritten by the next store", stacklevel=2)
            return {}
        if not isinstance(data, dict) or data.get("schema") != SCHEMA:
            warnings.warn(
                f"ignoring tuning cache at {self.path!r}: schema "
                f"{data.get('schema') if isinstance(data, dict) else None!r}"
                f" != {SCHEMA!r} (stale entries are discarded, not reused)",
                stacklevel=2)
            return {}
        entries = data.get("entries")
        return entries if isinstance(entries, dict) else {}

    def get(self, key: str) -> dict | None:
        entry = self.load().get(key)
        return entry if isinstance(entry, dict) else None

    def put(self, key: str, entry: dict) -> None:
        """Read-merge-replace with an atomic tmp-then-rename write."""
        if self.path is None:
            return
        entries = self.load()
        entries[key] = entry
        d = os.path.dirname(self.path) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"schema": SCHEMA, "entries": entries}, f,
                          indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, self.path)
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise


def _shape_of(config: dict, num_resources: int) -> dict:
    shape = {k: int(config.get(k, d)) for k, d in _SHAPE_DEFAULTS.items()}
    shape["R"] = int(num_resources)
    return shape


def apply_tuned(policy: str, engine: str, config: dict,
                num_resources: int = 1,
                cache: TuningCache | None = None) -> dict:
    """Fill unset launch knobs from the tuning cache, in place.

    Only knobs the caller left unset (absent or None) are filled —
    an explicit ``work_steps=``/``window=`` always wins over the cache.
    Returns telemetry for bench meta strings: ``{"tuned": 0|1,
    "cache_hit": 0|1}`` (``tuned`` = at least one knob was actually
    injected; ``cache_hit`` = the shape had a cache entry at all).
    """
    meta = {"tuned": 0, "cache_hit": 0}
    if engine == "reference" or not tuning_enabled():
        return meta
    cache = cache or TuningCache()
    shape = _shape_of(config, num_resources)
    entry = cache.get(shape_key(policy, engine, **shape))
    if entry is None:
        return meta
    meta["cache_hit"] = 1
    knobs = ["work_steps"] + (["window"] if engine == "pallas" else [])
    for knob in knobs:
        if config.get(knob) is None and entry.get(knob) is not None:
            config[knob] = int(entry[knob])
            meta["tuned"] = 1
    return meta


def _bitmatch(a, b) -> bool:
    for f in a._fields:
        x, y = getattr(a, f), getattr(b, f)
        if (x is None) != (y is None):
            return False
        if x is not None and not (np.asarray(x) == np.asarray(y)).all():
            return False
    return True


def _default_grids(engine: str, A_max: int, horizon: int):
    default_ws = A_max + 4  # resolve_work_steps' default bound
    ws = sorted({1, 2, 3, 4, 6, 8, default_ws, 2 * default_ws})
    windows: list[int | None] = [None]
    if engine == "pallas":
        for div in (2, 4, 8):
            # a window is the stream blocks' sublane extent: a multiple
            # of 8 on a TPU (kernels.common.resolve_windows)
            if horizon % div == 0 and (horizon // div) % 8 == 0:
                windows.append(horizon // div)
    return ws, windows


def autotune(workload, keys, *, policy: str = "bfjs", engine: str = "scan",
             work_steps_grid=None, window_grid=None, rounds: int = 3,
             cache: TuningCache | None = None,
             allow_interpret: bool = False, **config) -> dict:
    """Sweep ``work_steps``/``window`` for one launch shape and cache the
    verified winner.

    Runs ``monte_carlo_policy``'s underlying engine once per candidate on
    the SAME keys, round-robin best-of-``rounds`` timed (interleaved so
    machine-load drift hits every candidate equally), and rejects any
    candidate whose trajectory is not bit-identical to the untuned
    baseline or whose ``truncated`` is nonzero.  The winner (fastest
    verified candidate, baseline included) is stored under the launch's
    :func:`shape_key` and returned:

        {"work_steps": ..., "window": ..., "us": ..., "baseline_us": ...,
         "speedup": ..., "key": ..., "candidates": N, "rejected": M}

    ``engine="reference"`` has no launch knobs and is rejected; Pallas in
    interpret mode is rejected unless ``allow_interpret=True`` (interpret
    timings do not transfer to compiled kernels — DESIGN.md §11).
    """
    from repro.kernels.common import interpret_default

    from .api import get_policy

    if engine == "reference":
        raise ValueError("engine=\"reference\" has no launch knobs to tune")
    if engine == "pallas" and interpret_default() and not allow_interpret:
        raise ValueError(
            "refusing to autotune Pallas kernels in interpret mode: "
            "interpret timings are correctness-grade and do not transfer "
            "to compiled kernels (pass allow_interpret=True to override)")
    if not tuning_enabled():
        raise ValueError(
            f"tuning cache is disabled ({_ENV}=off); autotune would "
            "sweep and then discard the winner")
    cache = cache or TuningCache()
    run = get_policy(policy).monte_carlo
    horizon = int(config.get("horizon", 10_000))
    shape = _shape_of(config, workload.num_resources)
    ws_grid, win_grid = _default_grids(engine, shape["A_max"], horizon)
    if work_steps_grid is not None:
        ws_grid = sorted({int(w) for w in work_steps_grid})
    if window_grid is not None:
        win_grid = list(window_grid)

    base_cfg = dict(config)
    base_cfg.pop("work_steps", None)
    base_cfg.pop("window", None)

    def runner(ws, win):
        kw = dict(base_cfg)
        if ws is not None:
            kw["work_steps"] = ws
        if win is not None:
            kw["window"] = win
        return run(workload, keys, engine=engine, **kw)

    baseline = runner(None, None)
    jax_block = lambda r: r.queue_len.block_until_ready()
    jax_block(baseline)

    cands = [(ws, win) for ws in ws_grid for win in win_grid]
    results, rejected = {}, 0
    for c in list(cands):
        res = runner(*c)
        jax_block(res)
        if int(np.asarray(res.truncated).sum()) != 0 \
                or not _bitmatch(res, baseline):
            cands.remove(c)
            rejected += 1
            continue
        results[c] = res
    # round-robin best-of-N over the surviving candidates + the baseline
    best = {c: float("inf") for c in cands + [("baseline", None)]}
    for _ in range(max(rounds, 1)):
        for c in best:
            t0 = time.perf_counter()
            jax_block(runner(None, None) if c[0] == "baseline"
                      else runner(*c))
            best[c] = min(best[c], time.perf_counter() - t0)
    base_us = best.pop(("baseline", None)) * 1e6
    win_c = min(best, key=best.get)
    win_us = best[win_c] * 1e6
    if win_us > base_us:  # nothing beat the default: record the default
        win_c, win_us = (None, None), base_us
    key = shape_key(policy, engine, **shape)
    entry = {**shape, "policy": policy, "engine": engine,
             "work_steps": win_c[0], "window": win_c[1],
             "us": round(win_us, 3), "baseline_us": round(base_us, 3),
             "speedup": round(base_us / win_us, 4)}
    cache.put(key, entry)
    return {**entry, "key": key, "candidates": len(cands),
            "rejected": rejected}
