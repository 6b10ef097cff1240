"""Sweep path: Monte-Carlo ensembles through ``monte_carlo_policy``.

Set-up builds the cell's jitted ``monte_carlo_policy`` call (one program:
device stream generation plus the engine), checks that an
``engine="pallas"`` program holds the compiled kernel, and runs one warm-up
call.  The window then dispatches calls back to back, each on fresh keys
(``fold_in(seed, call)`` split into G), and each forced with
``block_until_ready`` before the next.  It opens at the first measured
call's dispatch and closes when the first call that completes after
``seconds`` has been forced.

``sweep_slots_per_s`` is G x T x calls over the window.  After the window,
the cell's traffic generator re-draws the streams of a sample of members
(one from each half of the ensemble, in one measured call drawn from the
seed), and the plain reference of the cell's policy replays them; every
per-slot queue length, occupancy and cumulative departure count must
match.  ``dropped`` and ``truncated`` must be 0 on every member of every
call.

The cell's traffic file gives the program's options: ``program_sizes``
(the configuration's sizes the program takes), ``program`` (further
keyword options, e.g. a kernel's window) and ``work_steps``.
"""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

import repro.core.engine as engine_api
from bench.harness import Check

TRAJ = ("queue_len", "occupancy", "departed")


def program(cell: dict, sizes: dict, sampler):
    """The cell's jitted ``monte_carlo_policy`` call, as a user runs it."""
    wl = engine_api.Workload(lam=sizes["lam_per_server"] * sizes["L"],
                             mu=sizes["mu"], sampler=sampler)
    kw = {k: sizes[k] for k in cell["program_sizes"]}
    kw.update(cell["program"], policy=cell["policy"], engine=cell["engine"],
              horizon=cell["horizon"], work_steps=cell["work_steps"])
    return jax.jit(functools.partial(engine_api.monte_carlo_policy, wl,
                                     **kw))


def run(ctx) -> dict:
    cell, sizes, traffic = ctx.cell, ctx.config["sizes"], ctx.traffic
    G, T = cell["G"], cell["horizon"]
    fn = program(cell, sizes, traffic.sampler(sizes))
    keys0 = traffic.call_keys(ctx.seed, 0, G)
    compiled = fn.lower(keys0).compile()
    if cell["engine"] == "pallas" and ctx.platform == "tpu" \
            and "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError("the pallas program holds no compiled kernel")
    jax.block_until_ready(compiled(keys0))          # warm-up call

    outs, traced_calls = [], 0
    win = ctx.window
    call = 1
    keys = traffic.call_keys(ctx.seed, call, G)
    jax.block_until_ready(keys)
    win.open()
    while True:
        with jax.profiler.TraceAnnotation("bench.sweep.call"):
            res = compiled(keys)
            jax.block_until_ready(res)
        outs.append(res)
        traced_calls += win.tracing
        win.poll()
        if time.perf_counter() - win.t_open >= ctx.seconds:
            break
        call += 1
        with jax.profiler.TraceAnnotation("bench.sweep.keys"):
            keys = traffic.call_keys(ctx.seed, call, G)
    win.close()
    calls = len(outs)
    slots = G * T * calls
    ctx.note(calls=calls, member_slots=slots)

    ctx.read_memory()
    host = [jax.device_get(r) for r in outs]
    del outs, res, compiled
    dropped = int(sum(np.sum(r.dropped) for r in host))
    truncated = int(sum(np.sum(r.truncated) for r in host))
    ctx.note(dropped=dropped, truncated=truncated,
             queue_max=int(max(np.max(r.queue_len) for r in host)))

    rng = np.random.default_rng(ctx.seed)
    c = int(rng.integers(calls))
    half = max(G // 2, 1)
    members = sorted({int(rng.integers(half)),
                      int(half + rng.integers(G - half)) if G > 1 else 0})
    keys = traffic.call_keys(ctx.seed, c + 1, G)
    mismatched, failed = 0, 0
    t0 = time.perf_counter()
    for g in members:
        want = ctx.reference.sweep(traffic.streams(keys[g], sizes, T), sizes)
        bad = np.zeros(T, bool)
        for f in TRAJ:
            bad |= np.asarray(getattr(host[c], f)[g]) != want[f]
        mismatched += int(bad.sum())
        failed += int(bad.any())
    ctx.note(reference_s=time.perf_counter() - t0,
             reference_members=f"call {c + 1} members {members}")
    bad_members = sum(int(np.sum((r.dropped > 0) | (r.truncated > 0)))
                      for r in host)
    return {
        "metrics": {"sweep_slots_per_s": slots / win.seconds},
        "attempted": calls * G,
        "failed": failed + bad_members,
        "checks": [Check("mismatched_slots", mismatched, 0),
                   Check("dropped", dropped, 0),
                   Check("truncated", truncated, 0)],
        "work": {"member_slots": slots, "calls": calls,
                 "traced_member_slots": G * T * traced_calls},
    }
