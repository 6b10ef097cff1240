"""Fleet sweep path: Monte-Carlo ensembles through ``monte_carlo_policy``
on a two-resource ``Workload`` whose servers each have a capacity of
their own (the configuration's fleet, ``capacities`` of its traffic
generator).

It sets up, times and checks as ``bench/paths/sweep.py`` does: set-up
builds the jitted call (stream generation plus the engine), checks that an
``engine="pallas"`` program holds the compiled kernel and runs one warm-up
call; the window dispatches calls back to back on fresh keys, each forced
before the next, and ``sweep_slots_per_s`` is G x T x calls over it.
After the window the generator re-draws the streams of one member from
each half of one measured call, and the plain reference replays them on
the same capacity plane: every per-slot queue length, both occupancy
planes and every cumulative departure count must match, and ``dropped``
and ``truncated`` must be 0 on every member of every call.

``work`` adds the program's ``steps`` (work steps run), summed over every
call and over the traced calls, for the per-layer metrics; the
diagnostics note ``bfs_placements``, the placements BF-S refills made.
"""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

import repro.core.engine as engine_api
from bench.harness import Check
from bench.paths.sweep import TRAJ


def program(cell: dict, sizes: dict, sampler, caps):
    """The cell's jitted ``monte_carlo_policy`` call, as a user runs it."""
    wl = engine_api.Workload(lam=sizes["lam"], mu=sizes["mu"],
                             sampler=sampler, num_resources=sizes["R"],
                             capacity=caps)
    kw = {k: sizes[k] for k in cell["program_sizes"]}
    kw.update(cell["program"], policy=cell["policy"], engine=cell["engine"],
              horizon=cell["horizon"], work_steps=cell["work_steps"])
    return jax.jit(functools.partial(engine_api.monte_carlo_policy, wl,
                                     **kw))


def run(ctx) -> dict:
    cell, sizes, traffic = ctx.cell, ctx.config["sizes"], ctx.traffic
    G, T = cell["G"], cell["horizon"]
    caps = traffic.capacities(sizes)
    fn = program(cell, sizes, traffic.sampler(sizes), caps)
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
    steps = [int(np.sum(r.steps)) for r in host]
    bfs = int(sum(np.sum(r.bfs_placements) for r in host))
    ctx.note(dropped=dropped, truncated=truncated,
             queue_max=int(max(np.max(r.queue_len) for r in host)),
             steps_per_member_slot=sum(steps) / slots,
             bfs_placements=bfs)

    rng = np.random.default_rng(ctx.seed)
    c = int(rng.integers(calls))
    half = max(G // 2, 1)
    members = sorted({int(rng.integers(half)),
                      int(half + rng.integers(G - half)) if G > 1 else 0})
    keys = traffic.call_keys(ctx.seed, c + 1, G)
    mismatched, failed, placed, ref_bfs = 0, 0, 0, 0
    t0 = time.perf_counter()
    for g in members:
        st = traffic.streams(keys[g], sizes, T)
        want = ctx.reference.sweep(st, sizes, caps)
        bad = np.zeros(T, bool)
        for f in TRAJ:
            got = np.asarray(getattr(host[c], f)[g])
            bad |= (got != want[f]).reshape(T, -1).any(axis=1)
        mismatched += int(bad.sum())
        failed += int(bad.any())
        placed += int(np.sum(st[0])) - want["dropped"] \
            - int(want["queue_len"][-1])
        ref_bfs += want["bfs_placements"]
    ctx.note(reference_s=time.perf_counter() - t0,
             reference_members=f"call {c + 1} members {members}",
             reference_bfs_share=ref_bfs / max(placed, 1),
             bfs_placements_match=ref_bfs == int(
                 sum(host[c].bfs_placements[g] for g in members)))
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
                 "traced_member_slots": G * T * traced_calls,
                 "steps": sum(steps),
                 "traced_steps": sum(steps[:traced_calls])},
    }
