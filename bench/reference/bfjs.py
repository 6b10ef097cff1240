"""Plain BF-J/S reference (paper Section IV), written for the benchmark.

The policy as the paper states it, over key-driven streams (see
``bench/traffic/poisson_uniform.py``), in float32 job sizes with every
server's load summed in one written-out order, so that a residual's last
bit, which decides best-fit ties, is the same in any correct
implementation.  It imports nothing of the program under test.

Per slot ``t``:

1. jobs whose departure slot is ``t`` leave; ``freed`` are the servers that
   lost a job;
2. the slot's arrivals take the lowest free places of the ``Qcap``-place
   queue, in arrival order (an arrival that finds no place is dropped);
3. BF-S: while some freed server (lowest index first) has room for a
   queued job, it takes the largest queued job that fits (lowest queue
   place on ties), with the next duration of the slot's sequential draws
   ``durs[t, 0], durs[t, 1], ...``;
4. BF-J: each of the slot's arrivals, in order, if still queued, goes to
   the tightest feasible server (least residual >= size, lowest index on
   ties) with its own duration ``durs[t, L*K + a]``.

A placed job takes its server's lowest empty job slot (of ``K``).  The
residual of a server is ``1 - load``, the load summed over its ``K`` job
slots pairwise (``x[:8] + x[8:]``, then halves again), and the cluster's
occupancy sums those loads over blocks of 8 servers in order, then the 8
partial sums pairwise.

The module gives a path what it asks of a reference: ``sweep`` (one
cluster's key-driven streams) and ``slot_bytes`` (the algorithmic bytes of
one slot step, for the roofline).
"""
from __future__ import annotations

import numpy as np

F32 = np.float32
INF = np.iinfo(np.int64).max


def row_sum(x: np.ndarray) -> np.ndarray:
    """Pairwise sum over the last axis (padded to a power of two)."""
    w = x.shape[-1]
    pad = (1 << (w - 1).bit_length()) - w
    if pad:
        x = np.concatenate([x, np.zeros(x.shape[:-1] + (pad,), x.dtype)], -1)
    while x.shape[-1] > 1:
        h = x.shape[-1] // 2
        x = x[..., :h] + x[..., h:]
    return x[..., 0]


def cluster_sum(col: np.ndarray) -> np.float32:
    """Sum over servers: blocks of 8 in order, then the 8 pairwise."""
    pad = -len(col) % 8
    col = np.concatenate([col, np.zeros(pad, col.dtype)])
    acc = col[0:8].copy()
    for b in range(1, len(col) // 8):
        acc = acc + col[8 * b:8 * b + 8]
    p = acc
    return ((p[0] + p[1]) + (p[2] + p[3])) + ((p[4] + p[5]) + (p[6] + p[7]))


def simulate(n, sizes, durs, *, L: int, K: int, Qcap: int,
             work_steps: int | None = None, slots: int | None = None
             ) -> dict[str, np.ndarray]:
    """Run BF-J/S over streams: ``n[t]`` arrivals of float32 ``sizes[t]``,
    ``durs[t]`` of width ``L*K + A_max``.  With ``work_steps``, at most
    that many placements a slot, and a slot that could place more is
    counted in ``truncated``; without, every slot runs to its end, as the
    paper states the policy.  Returns per-slot ``queue_len``,
    ``occupancy`` and cumulative ``departed``, and ``dropped`` and
    ``truncated``."""
    T = len(n) if slots is None else slots
    srv = np.zeros((L, K), F32)
    dep = np.full((L, K), INF, np.int64)
    queue = np.zeros(Qcap, F32)
    q_out = np.zeros(T, np.int64)
    occ_out = np.zeros(T, F32)
    dep_out = np.zeros(T, np.int64)
    departed = dropped = truncated = 0
    one = F32(1.0)
    for t in range(T):
        leaving = dep == t
        freed = leaving.any(axis=1)
        departed += int(leaving.sum())
        srv[leaving] = 0
        dep[leaving] = INF
        resid = one - row_sum(srv)

        k = int(n[t])
        places = np.flatnonzero(queue == 0)[:k]
        dropped += k - len(places)
        queue[places] = sizes[t, :len(places)]
        d = durs[t]

        def place(s, qi, dur):
            slot = np.flatnonzero(srv[s] == 0)
            slot = int(slot[0]) if len(slot) else 0
            srv[s, slot] = queue[qi]
            dep[s, slot] = t + int(dur)
            queue[qi] = 0
            resid[s] = one - row_sum(srv[s])

        steps, dc, a = 0, 0, 0
        while work_steps is None or steps < work_steps:
            held = queue > 0
            qmin = queue[held].min() if held.any() else np.inf
            fits = np.flatnonzero(freed & (resid >= qmin))
            if len(fits):
                s = int(fits[0])
                fitq = np.where(held & (queue <= resid[s]), queue, -np.inf)
                place(s, int(np.argmax(fitq)), d[min(dc, len(d) - 1)])
                dc += 1
            elif a < len(places):
                size = queue[places[a]]
                if size > 0:
                    feas = np.flatnonzero(resid >= size)
                    if len(feas):
                        s = int(feas[np.argmin(resid[feas])])
                        place(s, int(places[a]), d[L * K + a])
                a += 1
            else:
                break
            steps += 1
        held = queue > 0
        qmin = queue[held].min() if held.any() else np.inf
        left = queue[places[a:]]
        if (freed & (resid >= qmin)).any() or \
                ((left > 0) & (left <= resid.max())).any():
            truncated += 1
        q_out[t] = int(held.sum())
        occ_out[t] = cluster_sum(row_sum(srv))
        dep_out[t] = departed
    return {"queue_len": q_out, "occupancy": occ_out, "departed": dep_out,
            "dropped": dropped, "truncated": truncated}


def sweep(streams, sizes: dict) -> dict[str, np.ndarray]:
    """The reference over one cluster's streams ``(n, sizes, durs)``, every
    slot run to its end."""
    n, sz, durs = (np.asarray(x) for x in streams)
    return simulate(n, sz, durs, L=sizes["L"], K=sizes["K"],
                    Qcap=sizes["Qcap"])


WORD = 4


def slot_bytes(sizes: dict) -> int:
    """Bytes one member-slot of BF-J/S must move, whatever engine runs it:
    the ``L x K`` job-slot sizes and departure slots and the ``Qcap`` queued
    sizes, 32 bits each, read and written once; and the slot's ``A_max``
    arrivals (a size and a duration each) read."""
    state = 2 * sizes["L"] * sizes["K"] + sizes["Qcap"]
    return 2 * WORD * state + 2 * WORD * sizes["A_max"]
