"""Plain multi-resource BF-J/S reference (paper Section VIII, the
alignment-score Best-Fit), with a capacity of its own for every server,
written for the benchmark.

The policy as stated, event by event, over key-driven streams (see
``bench/traffic/fleet_poisson_uniform.py``), in exact integers on the
``2**16`` grid: a demand ``d`` in (0, 1] of a resource takes
``max(rint(d * 2**16), 1)`` units of it, a capacity ``c`` takes
``rint(c * 2**16)``.  It imports nothing of the program under test.

Per slot ``t``:

1. jobs whose departure slot is ``t`` leave; ``freed`` are the servers that
   lost a job;
2. the slot's arrivals take the lowest free places of the ``Qcap``-place
   queue, in arrival order (an arrival that finds no place is dropped),
   each with its own duration ``durs[t, -A_max + a]``;
3. BF-S: each freed server, lowest index first, takes queued jobs while
   one fits in every resource: the one with the largest total demand
   (earliest arrival on ties);
4. BF-J: each of the slot's arrivals, in order, if still queued, goes to
   the server with room for it in every resource whose alignment score
   ``sum_r avail_r * demand_r`` is least (lowest index on ties), where
   ``avail`` is the server's capacity less what it holds.

The module gives a path what it asks of a reference: ``sweep`` (one
cluster's key-driven streams on its capacity plane) and ``slot_bytes``
(the algorithmic bytes of one slot step, for the roofline).
"""
from __future__ import annotations

import numpy as np

RES = 1 << 16
WORD = 4
INT64_MAX = np.iinfo(np.int64).max


def grid(x) -> np.ndarray:
    """Demands or capacities on the integer grid (float32 values scaled
    exactly, rounded half to even)."""
    return np.rint(np.asarray(x, np.float64) * RES).astype(np.int64)


def simulate(n, sizes, durs, caps, *, K: int, Qcap: int
             ) -> dict[str, np.ndarray]:
    """Run BF-J/S-MR over streams: ``n[t]`` arrivals with float32 demand
    vectors ``sizes[t]`` (``(A_max, R)``), their durations in the last
    ``A_max`` lanes of ``durs[t]``, on servers of capacity ``caps``
    (``(L, R)``).  Every slot runs to its end.  Returns per-slot
    ``queue_len``, ``occupancy`` (``(T, R)``, the cluster's total per
    resource, in servers) and cumulative ``departed``, and ``dropped`` and
    ``bfs_placements``."""
    T, A_max, R = sizes.shape
    cap = grid(caps)
    L = cap.shape[0]
    dem = np.zeros((L, K, R), np.int64)
    dep = np.full((L, K), -1, np.int64)
    occ = np.zeros((L, R), np.int64)
    qdem = np.zeros((Qcap, R), np.int64)
    qdur = np.zeros(Qcap, np.int64)
    qseq = np.full(Qcap, -1, np.int64)         # -1: an empty place
    q_out = np.zeros(T, np.int64)
    occ_out = np.zeros((T, R), np.float32)
    dep_out = np.zeros(T, np.int64)
    departed = dropped = bfs = seq = 0
    lanes = durs.shape[-1] - A_max

    def place(s, qi, t):
        slot = np.flatnonzero(dep[s] < 0)
        if not len(slot):
            raise RuntimeError(f"server {s} holds {K} jobs already")
        dem[s, slot[0]] = qdem[qi]
        dep[s, slot[0]] = t + qdur[qi]
        occ[s] += qdem[qi]
        qseq[qi] = -1

    for t in range(T):
        leaving = dep == t
        freed = np.flatnonzero(leaving.any(axis=1))
        departed += int(leaving.sum())
        occ -= (dem * leaving[..., None]).sum(axis=1)
        dem[leaving] = 0
        dep[leaving] = -1

        k = int(n[t])
        places = np.flatnonzero(qseq < 0)[:k]
        dropped += k - len(places)
        qdem[places] = np.maximum(grid(sizes[t, :len(places)]), 1)
        qdur[places] = durs[t, lanes:lanes + len(places)]
        qseq[places] = seq + np.arange(len(places))
        seq += k

        total = qdem.sum(axis=1)
        for s in freed:
            while True:
                fits = (qseq >= 0) & (qdem <= cap[s] - occ[s]).all(axis=1)
                if not fits.any():
                    break
                best = total == total[fits].max()
                cand = np.flatnonzero(fits & best)
                place(s, cand[np.argmin(qseq[cand])], t)
                bfs += 1
        for qi in places:
            if qseq[qi] < 0:
                continue
            avail = cap - occ
            feas = (qdem[qi] <= avail).all(axis=1)
            if feas.any():
                score = np.where(feas, avail @ qdem[qi], INT64_MAX)
                place(int(np.argmin(score)), qi, t)

        q_out[t] = int((qseq >= 0).sum())
        occ_out[t] = occ.sum(axis=0).astype(np.float32) / np.float32(RES)
        dep_out[t] = departed
    return {"queue_len": q_out, "occupancy": occ_out, "departed": dep_out,
            "dropped": dropped, "bfs_placements": bfs}


def sweep(streams, sizes: dict, caps) -> dict[str, np.ndarray]:
    """The reference over one cluster's streams ``(n, sizes, durs)`` on
    the ``(L, R)`` capacity plane ``caps``."""
    n, sz, durs = (np.asarray(x) for x in streams)
    return simulate(n, sz, durs, np.asarray(caps), K=sizes["K"],
                    Qcap=sizes["Qcap"])


def slot_bytes(sizes: dict) -> int:
    """Bytes one member-slot of BF-J/S-MR must move, whatever engine runs
    it: the ``L x K`` job-slot demands of each of the ``R`` resources and
    departure slots and the ``R x Qcap`` queued demands, 32 bits each,
    read and written once; the ``L x R`` capacities and the slot's
    ``A_max`` arrivals (``R`` demands and a duration each) read."""
    L, K, R = sizes["L"], sizes["K"], sizes["R"]
    state = (R + 1) * L * K + R * sizes["Qcap"]
    return 2 * WORD * state + WORD * (L * R + (R + 1) * sizes["A_max"])
