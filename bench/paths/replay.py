"""Replay path: a trace streamed through ``stream_policy``, supervised
and, where the cell's traffic file says ``"checkpoint": true``,
checkpointed at every chunk, as a production trace is replayed.

The trace comes from the configuration's traffic generator
(``trace(sizes, params, seed)``), made in set-up from ``--seed``.  It
reaches the program as row pieces (``Trace`` objects, each task's size as
both its cpu and its memory, so the program's max(cpu, mem) is the size),
re-bucketed by the program's ``stream_chunks_from_trace`` into windows of
``chunk_slots`` slots.  The benchmark wraps that source: it times each
``next()`` (the host ingestion the program does), marks it with a host
span, and stops yielding once the window has run ``seconds``.

Set-up is the first ``warmup_chunks`` chunks of the same call (they
compile the first-chunk and carried-state programs and bring the cluster
to its steady occupancy).  The window opens when the source is asked for
the first chunk after them and closes when ``stream_policy`` returns with
every chunk drained.  ``replay_slots_per_s`` is the slots of the chunks
pulled in the window over its length.  A trace that runs out before the
window's end is an error, never a shorter window.

After the window, with checkpoints: a second ``stream_policy`` call
resumes from the last checkpoint and runs one more chunk, and the plain
reference of the cell's policy replays the raw trace from slot 0 through
that chunk; the per-slot queue length, occupancy and cumulative
departures of every chunk the run executed (read back from its
checkpoint), of the returned tail and of the resumed chunk must match it.
Without checkpoints, the returned tail is compared.  ``dropped`` and
``truncated`` must be 0.
"""
from __future__ import annotations

import os
import shutil
import time

import jax
import numpy as np

import repro.core.engine as engine_api
from repro.core.trace import Trace
from bench.harness import Check

TRAJ = ("queue_len", "occupancy", "departed")


class TimedSource:
    """Wraps the program's chunk source: times each ``next()`` and stops
    after ``stop_after`` chunks, or once ``deadline()`` says so."""

    def __init__(self, inner, stop_after: int | None, on_pull=None,
                 deadline=None):
        self.inner = inner
        self.stop_after = stop_after
        self.on_pull = on_pull
        self.deadline = deadline
        self.pulled = 0
        self.window_pulled = 0
        self.window_ingest_s = 0.0
        self.in_window = False
        self.last_pull = None
        self.widest_gap = (0.0, None)      # (seconds, chunk index)

    def __iter__(self):
        return self

    def __next__(self):
        if self.on_pull is not None:
            self.on_pull(self.pulled)
        if self.stop_after is not None and self.pulled >= self.stop_after:
            raise StopIteration
        if self.in_window and self.deadline():
            raise StopIteration
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.replay.pull"):
            chunk = next(self.inner)
        dt = time.perf_counter() - t0
        if self.in_window:
            self.window_pulled += 1
            self.window_ingest_s += dt
            if self.last_pull is not None:
                self.widest_gap = max(self.widest_gap,
                                      (t0 - self.last_pull, self.pulled))
        self.last_pull = t0
        self.pulled += 1
        return chunk


def trace_pieces(tr: dict, rows: int):
    """The trace as ``Trace`` row pieces, as a CSV reader would give it."""
    for i in range(0, len(tr["arrival_slots"]), rows):
        size = tr["size"][i:i + rows]
        yield Trace(tr["arrival_slots"][i:i + rows], size, size,
                    tr["durations"][i:i + rows])


def source(tr: dict, cell: dict, sizes: dict):
    return engine_api.stream_chunks_from_trace(
        trace_pieces(tr, cell["trace_rows"]), chunk_slots=cell["chunk_slots"],
        A_max=sizes["A_max"])


def stream(chunks, cell: dict, sizes: dict, ckpt_dir: str | None,
           resume: bool = False):
    """The cell's ``stream_policy`` call."""
    sup = engine_api.Supervisor(compute_timeout=cell["compute_timeout_s"],
                                stage_timeout=cell["stage_timeout_s"])
    return engine_api.stream_policy(
        chunks, policy=cell["policy"], engine=cell["engine"],
        supervisor=sup, checkpoint_dir=ckpt_dir, resume=resume,
        trajectory="tail", work_steps=cell["work_steps"],
        **{k: sizes[k] for k in cell["program_sizes"]})


def checkpoint_planes(ckpt_dir: str, step: int) -> dict:
    """The per-slot planes of chunk ``step - 1``, from the checkpoint the
    program wrote after it."""
    with np.load(os.path.join(ckpt_dir, f"step_{step:08d}",
                              "arrays.npz")) as z:
        return {f: z[f"partial/{f}"] for f in TRAJ}


def run(ctx) -> dict:
    cell, sizes = ctx.cell, ctx.config["sizes"]
    cs, warm = cell["chunk_slots"], cell["warmup_chunks"]
    tr = ctx.traffic.trace(sizes, cell, ctx.seed)
    ckpt_dir = os.path.join(ctx.run_dir, "checkpoints") \
        if cell["checkpoint"] else None
    win = ctx.window

    def on_pull(index):
        if index == warm:
            src.in_window = True
            win.open()
        elif index > warm:
            win.poll()

    src = TimedSource(source(tr, cell, sizes), None, on_pull=on_pull,
                      deadline=lambda: time.perf_counter() - win.t_open
                      >= ctx.seconds)
    with jax.profiler.TraceAnnotation("bench.replay.stream_policy"):
        res = stream(src, cell, sizes, ckpt_dir)
        jax.block_until_ready(res.queue_len)
    win.close()
    executed = src.pulled
    if executed <= warm:
        raise RuntimeError("the window ran no chunk")
    if win.seconds < ctx.seconds:
        raise RuntimeError(f"the trace ran out {win.seconds:.1f} s into the "
                           f"window; raise trace_slots")
    slots = src.window_pulled * cs
    ctx.note(chunks_in_window=src.window_pulled,
             chunks_behind=res.chunks_behind,
             host_stall_us=res.host_stall_us, retries=res.retries,
             quarantined=res.quarantined,
             widest_pull_gap=dict(zip(("s", "chunk"), src.widest_gap)))
    ctx.read_memory()
    tail = jax.device_get(res)
    ctx.note(dropped=int(tail.dropped), truncated=int(tail.truncated))

    got = [{f: np.asarray(getattr(tail, f)) for f in TRAJ}]
    spans = [((executed - 1) * cs, executed * cs)]
    last = tail
    if ckpt_dir is not None:
        # every executed chunk from its checkpoint, and one more chunk
        # resumed from the last checkpoint
        last = jax.device_get(stream(
            TimedSource(source(tr, cell, sizes), executed + 1), cell, sizes,
            ckpt_dir, resume=True))
        got += [checkpoint_planes(ckpt_dir, i + 1) for i in range(executed)]
        got.append({f: np.asarray(getattr(last, f)) for f in TRAJ})
        spans += [(i * cs, (i + 1) * cs) for i in range(executed)]
        spans.append((executed * cs, (executed + 1) * cs))
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    t0 = time.perf_counter()
    want = ctx.reference.replay(tr, max(hi for _, hi in spans), sizes)
    ctx.note(reference_s=time.perf_counter() - t0)
    ctx.note(queue_max=int(max(np.max(p["queue_len"]) for p in got)),
             queue_at_chunk_ends=[int(p["queue_len"][-1]) for p in got[1:]])
    mismatched, failed = 0, 0
    for planes, (lo, hi) in zip(got, spans):
        bad = np.zeros(hi - lo, bool)
        for f in TRAJ:
            bad |= np.asarray(planes[f]) != want[f][lo:hi]
        mismatched += int(bad.sum())
        failed += int(bad.any())
    return {
        "metrics": {"replay_slots_per_s": slots / win.seconds},
        "attempted": len(got),
        "failed": failed,
        "checks": [Check("mismatched_slots", mismatched, 0),
                   Check("dropped", int(last.dropped), 0),
                   Check("truncated", int(last.truncated), 0)],
        "work": {"slots": slots, "chunks": src.window_pulled,
                 "ingest_s": src.window_ingest_s},
    }
