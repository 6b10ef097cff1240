#!/usr/bin/env python3
"""Bring-up smoke run of the scheduler's accelerator paths on a TPU.

Drives the three paths users run on the accelerator, through their normal
entry points, at a real cluster size, and checks every result against an
independent reference:

  A. Monte-Carlo sweep: ``monte_carlo_policy`` for bfjs, vqs, vqs-bf and
     bfjs-mr on ``engine="scan"`` and on ``engine="pallas", strict=True``
     (the compiled kernel; a fallback to scan is an error).  Pallas must
     bit-match scan, both must bit-match ``engine="reference"`` on the same
     device-generated streams, and bfjs-mr's reference is the event-driven
     numpy oracle (``core/multi_resource.py``) fed the streams copied off
     the device.
  B. Streaming replay: ``stream_policy`` under a ``Supervisor`` with
     ``audit=True``, chunk by chunk, bit-matched against one-shot
     ``run_policy_streams`` over the same slots.
  C. Serving admission: ``LiveAdmission`` at 1000 replicas,
     placement-for-placement against the host ``AdmissionController``.

With ``--chips 4`` it runs only the mesh-sharded ensemble: bfjs and vqs-bf,
scan and pallas, G=32 over four chips, bit-matched against the same keys
on one chip in the same process.

It exits nonzero, before any phase, when JAX finds no TPU, and at the
first failed check.  Its last stdout line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  The times it
prints include compilation: this is a smoke run, not a benchmark.

    python chip_smoke.py             # one chip: phases A, B, C
    python chip_smoke.py --chips 4   # four chips: the sharded ensemble only
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import warnings
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0

#: Phase A: the paper's 1000-server cluster (benchmarks/fig5.py), K job
#: slots per server, U(0.1, 0.6) sizes (mean 0.35), mean service 1/MU = 100
#: slots, and LAM_PER_SERVER * L arrivals/slot for rho = LAM_PER_SERVER *
#: 0.35 / MU = 0.875 — stability_bench's operating point scaled from L=8 to
#: L=1000.  A_max=64 clips Poisson(25) with probability ~1e-11 per slot.
SWEEP = dict(L=1000, K=16, Qcap=4096, A_max=64, horizon=2000, window=40,
             work_steps=96)
SWEEP_G = 8
LAM_PER_SERVER, MU, J = 0.025, 0.01, 4
POLICIES = ("bfjs", "vqs", "vqs-bf", "bfjs-mr")
#: ensemble members checked against engine="reference", over their first
#: REF_SLOTS slots (a trajectory's prefix depends only on its streams'
#: prefix; the serial oracles step server by server, far slower than the
#: engines at L=1000)
REF_MEMBERS = (0, SWEEP_G - 1)
REF_SLOTS = 500

#: Phase B: benchmarks/fig5.py's full-scale synthetic Google trace on its
#: 640 servers, the first CHUNKS * CHUNK_SLOTS slots of it streamed.
REPLAY = dict(L=640, K=16, Qcap=4096, chunk_slots=1024, chunks=4,
              n_tasks=1_000_000, trace_horizon=1_300_000, trace_seed=4,
              mean_duration=6000.0, J=7)

#: Phase C: 1000 serving replicas, ADMIT_BATCH requests per tick.
ADMIT = dict(replicas=1000, Qcap=4096, ticks=300, batch=32,
             done_prob=0.012, tick_width=96)

#: --chips 4: the sharded ensemble at Phase A's widths.
MESH = dict(SWEEP, horizon=400)
MESH_G = 32

ENGINES = ("scan", "pallas")
TRAJ = ("queue_len", "occupancy", "departed", "dropped", "truncated",
        "preempted", "requeued", "lost")


class SmokeFailure(AssertionError):
    """A check of the smoke run failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"ok: {what}")


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check_match(a, b, what: str) -> None:
    """Check that two results agree bit for bit on every trajectory field;
    a failure names each differing field and its first differing index."""
    diffs = []
    for f in TRAJ:
        x, y = getattr(a, f), getattr(b, f)
        if x is None and y is None:
            continue
        if x is None or y is None:
            diffs.append(f"{f} missing on one side")
            continue
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape:
            diffs.append(f"{f} shapes {x.shape} vs {y.shape}")
        elif not np.array_equal(x, y):
            diffs.append(f"{f} differs first at "
                         f"{tuple(int(v) for v in np.argwhere(x != y)[0])}")
    check(not diffs, what + (f" ({'; '.join(diffs)})" if diffs else ""))


def member(res, g: int):
    return jax.tree.map(lambda x: x[g], res)


def sampler_1(key, n):
    return jax.random.uniform(key, (n,), minval=0.1, maxval=0.6)


def sampler_2(key, n):
    return jax.random.uniform(key, (n, 2), minval=0.1, maxval=0.6)


def workload(policy: str, L: int):
    from repro.core.engine import Workload
    lam = LAM_PER_SERVER * L
    if policy == "bfjs-mr":
        return Workload(lam=lam, mu=MU, sampler=sampler_2, num_resources=2,
                        capacity=(1.0, 1.0))
    return Workload(lam=lam, mu=MU, sampler=sampler_1)


def policy_config(policy: str, sizes: dict) -> dict:
    cfg = dict(sizes)
    if policy.startswith("vqs"):
        cfg["J"] = J
    return cfg


def engine_config(policy: str, engine: str, sizes: dict) -> dict:
    """``monte_carlo_policy`` keywords; the kernel path is ``strict``: a
    fallback to scan is an error, not a pass."""
    cfg = policy_config(policy, sizes)
    return dict(cfg, strict=True) if engine == "pallas" else cfg


def memory_line() -> str:
    stats = jax.devices()[0].memory_stats() or {}
    return (f"device memory: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')} bytes_limit="
            f"{stats.get('bytes_limit')}")


def compile_all(jobs: dict) -> dict:
    """AOT-compile ``{label: (fn, args)}``: each program is traced here in
    turn and compiled on a thread pool (compilation releases the
    interpreter lock, so the programs compile at once).  Logs each
    program's compile seconds and the wall time; returns
    ``{label: compiled}``."""
    lowered = {label: jax.jit(fn).lower(*args)
               for label, (fn, args) in jobs.items()}

    def timed(low):
        t0 = time.perf_counter()
        return low.compile(), time.perf_counter() - t0

    t0 = time.perf_counter()
    with ThreadPoolExecutor(len(lowered)) as pool:
        futures = {label: pool.submit(timed, low)
                   for label, low in lowered.items()}
        done = {label: f.result() for label, f in futures.items()}
    log(f"compiled {len(done)} programs at once: wall_s="
        f"{time.perf_counter() - t0:.3f}; " + ", ".join(
            f"{label} compile_s={s:.3f}" for label, (_, s) in done.items()))
    return {label: c for label, (c, _) in done.items()}


def run(compiled, *args, label: str, kernel: bool = False):
    """Run a compiled program once (timed) and return its device result.
    ``kernel=True`` requires a Mosaic kernel in the program: the Pallas
    path ran compiled, not interpreted."""
    if kernel:
        check("tpu_custom_call" in compiled.as_text(),
              f"{label}: compiled program holds the Mosaic kernel")
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    mem = compiled.memory_analysis()
    log(f"{label}: run_s={time.perf_counter() - t0:.3f} hbm argument="
        f"{mem.argument_size_in_bytes} output={mem.output_size_in_bytes} "
        f"temp={mem.temp_size_in_bytes}")
    return out


def prefix(res, n: int):
    """The first ``n`` slots of a one-member result's per-slot planes."""
    return res._replace(queue_len=res.queue_len[:n],
                        occupancy=res.occupancy[:n],
                        departed=res.departed[:n])


def reference_member(policy: str, wl, streams, g: int, sizes: dict,
                     slots: int):
    """``engine="reference"`` for the first ``slots`` slots of ensemble
    member ``g``, on the streams the device generated for it."""
    from repro.core.engine import run_policy_streams
    cfg = policy_config(policy, sizes)
    for knob in ("horizon", "window", "work_steps"):
        cfg.pop(knob)
    st = jax.tree.map(lambda x: x[g, :slots], streams)
    if policy == "bfjs-mr":
        # the event-driven numpy oracle, fed the device's streams
        st = jax.device_get(st)
        cfg["capacity"] = wl.capacity
    return run_policy_streams(st, policy=policy, engine="reference", **cfg)


def phase_sweep(sizes: dict, G: int, ref_members, ref_slots: int,
                policies=POLICIES):
    from repro.core.engine import ensemble_streams, monte_carlo_policy
    log(f"phase A: monte_carlo_policy G={G} {sizes} "
        f"lam={LAM_PER_SERVER * sizes['L']} mu={MU} "
        f"J={J}; reference on the first {ref_slots} slots of members "
        f"{list(ref_members)}")
    keys = jax.random.split(jax.random.PRNGKey(SEED), G)
    compiled = compile_all({
        (policy, engine): (functools.partial(
            monte_carlo_policy, workload(policy, sizes["L"]), policy=policy,
            engine=engine, **engine_config(policy, engine, sizes)), (keys,))
        for policy in policies for engine in ENGINES})
    for policy in policies:
        wl = workload(policy, sizes["L"])
        cfg = policy_config(policy, sizes)
        out = {}
        for engine in ENGINES:
            res = out[engine] = jax.device_get(run(
                compiled[policy, engine], keys, label=f"A {policy}/{engine}",
                kernel=engine == "pallas"))
            check(int(np.max(res.truncated)) == 0
                  and int(np.max(res.dropped)) == 0,
                  f"A {policy}/{engine}: truncated == dropped == 0 on all "
                  f"{G} members")
            tail = res.queue_len[:, -sizes["horizon"] // 4:]
            log(f"A {policy}/{engine}: mean queue over the last quarter "
                f"{float(np.mean(tail)):.2f}, departed "
                f"{int(np.sum(res.departed[:, -1]))}")
        check_match(out["scan"], out["pallas"],
                    f"A {policy}: pallas bit-matches scan on all {G} "
                    f"members")
        streams = ensemble_streams(wl, keys, L=cfg["L"], K=cfg["K"],
                                   A_max=cfg["A_max"],
                                   horizon=cfg["horizon"])
        for g in ref_members:
            t0 = time.perf_counter()
            ref = reference_member(policy, wl, streams, g, sizes,
                                   ref_slots)
            jax.block_until_ready(ref.queue_len)
            log(f"A {policy}/reference member {g}: "
                f"wall_s={time.perf_counter() - t0:.3f}")
            check_match(prefix(member(out["scan"], g), ref_slots), ref,
                        f"A {policy}: scan and pallas bit-match the "
                        f"reference on member {g}, slots 0-{ref_slots - 1}")
        del streams
    log(memory_line())


def phase_replay(sizes: dict):
    from repro.core import synthesize_google_like_trace
    from repro.core.engine import (Supervisor, iter_stream_chunks,
                                   make_streams, run_policy_streams,
                                   stream_chunks_from_trace, stream_policy,
                                   streams_from_trace)
    from repro.core.trace import Trace
    L, K, Qcap = sizes["L"], sizes["K"], sizes["Qcap"]
    cs, nc = sizes["chunk_slots"], sizes["chunks"]
    H = cs * nc
    log(f"phase B: stream_policy engine=scan (the kernels keep their state "
        f"in VMEM for one launch and cannot carry it across chunks), "
        f"supervised, audit=True; L={L} K={K} Qcap={Qcap} {nc} chunks of "
        f"{cs} slots")

    def supervised(chunks, policy, cfg):
        sup = Supervisor(compute_timeout=900.0, stage_timeout=300.0)
        t0 = time.perf_counter()
        res = stream_policy(chunks, policy=policy, engine="scan",
                            supervisor=sup, audit=True, **cfg)
        jax.block_until_ready(res.queue_len)
        log(f"B {policy}: wall_s={time.perf_counter() - t0:.3f} "
            f"chunks_behind={res.chunks_behind} retries={res.retries} "
            f"quarantined={res.quarantined}")
        check(res.quarantined == 0, f"B {policy}: no chunk quarantined")
        return res

    # vqs-bf replays the fig5 trace (sizes max(cpu, mem), paper §VII)
    tr = synthesize_google_like_trace(
        sizes["n_tasks"], sizes["trace_horizon"], seed=sizes["trace_seed"],
        mean_duration=sizes["mean_duration"])
    keep = tr.arrival_slots < H
    head = Trace(tr.arrival_slots[keep], tr.cpu[keep], tr.mem[keep],
                 tr.durations[keep])
    A_max = int(np.bincount(head.arrival_slots).max())
    log(f"B trace: {len(tr)} tasks over {sizes['trace_horizon']} slots, "
        f"{len(head)} in the first {H} slots, peak {A_max} arrivals/slot")
    cfg = dict(L=L, K=K, Qcap=Qcap, A_max=A_max, J=sizes["J"])
    rows = 4096
    pieces = (Trace(head.arrival_slots[i:i + rows], head.cpu[i:i + rows],
                    head.mem[i:i + rows], head.durations[i:i + rows])
              for i in range(0, len(head), rows))
    streamed = supervised(
        stream_chunks_from_trace(pieces, chunk_slots=cs, A_max=A_max),
        "vqs-bf", cfg)
    # the re-bucketing source ends its last window at the last arrival
    one = run_policy_streams(
        streams_from_trace(head, horizon=streamed.queue_len.shape[-1],
                           A_max=A_max),
        policy="vqs-bf", engine="scan", **cfg)
    check_match(streamed, one,
                "B vqs-bf: streamed trace bit-matches one-shot "
                "run_policy_streams")

    log("B bfjs: replays make_streams streams at rho = 0.875 (its refills "
        "draw durations from a sequential region a trace does not have)")
    cfg = dict(L=L, K=K, Qcap=Qcap, A_max=SWEEP["A_max"])
    wl = workload("bfjs", L)
    st = make_streams(jax.random.PRNGKey(SEED + 1), wl.lam, MU, sampler_1,
                      L=L, K=K, A_max=cfg["A_max"], horizon=H)
    streamed = supervised(iter_stream_chunks(st, cs), "bfjs", cfg)
    one = run_policy_streams(st, policy="bfjs", engine="scan", **cfg)
    check_match(streamed, one,
                "B bfjs: streamed chunks bit-match one-shot "
                "run_policy_streams")
    log(memory_line())


def phase_admission(sizes: dict):
    from repro.cluster.admission import AdmissionController, PendingJob
    from repro.serving.live import LiveAdmission
    R, ticks, B = sizes["replicas"], sizes["ticks"], sizes["batch"]
    log(f"phase C: LiveAdmission vs AdmissionController, {R} replicas, "
        f"Qcap={sizes['Qcap']}, {ticks} ticks of {B} admits + completions")
    rng = np.random.default_rng(SEED)
    host = AdmissionController(R)
    live = LiveAdmission(R, Qcap=sizes["Qcap"],
                         tick_width=sizes["tick_width"])
    size_of, active, rid, placements, peak_q = {}, {}, 0, 0, 0
    t_live = 0.0
    for t in range(ticks):
        jobs = []
        for _ in range(B):
            j = PendingJob(rid=rid, frac=float(rng.uniform(0.05, 0.95)))
            size_of[rid] = j.size
            jobs.append(j)
            rid += 1
        t0 = time.perf_counter()
        placed = live.admit(list(jobs))
        t_live += time.perf_counter() - t0
        check_quiet(host.admit(list(jobs)) == placed, f"C admit tick {t}")
        active.update(placed)
        done = [r for r in list(active) if rng.uniform() < sizes["done_prob"]]
        events = [(active.pop(r), size_of[r]) for r in done]
        host_placed = []
        for rep, size in events:
            host.release(rep, size)
        for rep in sorted({rep for rep, _ in events}):
            host_placed += host.refill(rep)
        t0 = time.perf_counter()
        placed2 = live.tick(events)
        t_live += time.perf_counter() - t0
        check_quiet(host_placed == placed2, f"C tick {t}")
        active.update(placed2)
        placements += len(placed) + len(placed2)
        q = live.queue_len()
        check_quiet(host.queue_len() == q
                    and np.array_equal(host.residual, live.residual),
                    f"C queue and residuals, tick {t}")
        peak_q = max(peak_q, q)
    check(live.dropped == 0 and peak_q > 0,
          f"C: {ticks * 2} admit/tick requests, {placements} placements "
          f"identical to the host controller (peak queue {peak_q}, "
          f"no drops)")
    log(f"C: live admission wall_s={t_live:.3f} over {ticks * 2} calls "
        f"(includes compilation)")
    log(memory_line())


def check_quiet(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def phase_mesh(sizes: dict, G: int):
    from repro.core.engine import monte_carlo_policy
    devs = jax.devices()[:4]
    log(f"--chips 4: monte_carlo_policy G={G} {sizes} over {len(devs)} "
        f"chips vs one chip")
    keys = jax.random.split(jax.random.PRNGKey(SEED), G)
    placements = (("1 chip", {}), ("4 chips", {"devices": devs}))
    compiled = compile_all({
        (policy, engine, name): (functools.partial(
            monte_carlo_policy, workload(policy, sizes["L"]), policy=policy,
            engine=engine, **mesh_kw, **engine_config(policy, engine, sizes)),
            (keys,))
        for policy in ("bfjs", "vqs-bf") for engine in ENGINES
        for name, mesh_kw in placements})
    for policy in ("bfjs", "vqs-bf"):
        for engine in ENGINES:
            runs = {name: run(compiled[policy, engine, name], keys,
                              label=f"mesh {policy}/{engine} {name}",
                              kernel=engine == "pallas")
                    for name, _ in placements}
            shards = runs["4 chips"].queue_len.addressable_shards
            spans = [(s.device, range(G)[s.index[0]]) for s in shards]
            log(f"mesh {policy}/{engine}: queue_len shards " + ", ".join(
                f"{d}: members {r.start}-{r.stop - 1}" for d, r in spans))
            check(len({s.device for s in shards}) == len(devs),
                  f"mesh {policy}/{engine}: the ensemble spans "
                  f"{len(devs)} chips")
            check(int(np.max(runs["1 chip"].truncated)) == 0,
                  f"mesh {policy}/{engine}: truncated == 0")
            check_match(runs["1 chip"], runs["4 chips"],
                        f"mesh {policy}/{engine}: 4 chips bit-match 1 chip on "
                        f"all {G} members")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded ensemble on four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); this smoke run needs the chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.compile_cache import use_compile_cache
    from repro.kernels.common import GracefulDegradationWarning
    # launch knobs are passed explicitly; never read a tuning cache
    os.environ["REPRO_TUNING_CACHE"] = "off"
    warnings.simplefilter("error", GracefulDegradationWarning)
    cache = use_compile_cache()
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"smoke run, not a benchmark: times include compilation; "
        f"{len(devices)} x {devices[0].device_kind}; compile cache {cache} "
        f"({entries} entries at start)")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(MESH, MESH_G)
    else:
        phase_sweep(SWEEP, SWEEP_G, REF_MEMBERS, REF_SLOTS)
        phase_replay(REPLAY)
        phase_admission(ADMIT)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
