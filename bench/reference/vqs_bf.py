"""Plain VQS-BF reference (paper Section VI), written for the benchmark.

A direct, event-driven transcription of the policy as the paper states it,
on the exact integer size grid (capacity 2**16 per server).  It imports
nothing of the program under test: the program's engines must reproduce
its per-slot queue length, occupancy and cumulative departures bit for
bit.

Per slot ``t``:

1. jobs whose departure slot is ``t`` leave; ``freed`` are the servers that
   lost a job, ``emptied`` those of them that are now empty;
2. the slot's arrivals join their virtual queue (partition I, paper Eq. 6),
   in arrival order;
3. the servers to visit are the freed ones, the emptied ones, those
   subscribed to a queue that just received a job, and, while any job
   waits, every server in the empty set.  In ascending server order, an
   empty server renews its configuration to the max-weight row of
   K_RED (paper Eq. 7, 8) and joins the empty set; then it is served:
   (i) with k_1 = 1 and no resident type-1 job, the largest type-1 job that
   fits; (ii) largest-fit-first from VQ_{j*} up to k_{j*} resident jobs of
   that type; (iii) the largest fitting job over all virtual queues until
   nothing fits.  A server that finds a queue it wants empty subscribes to
   it.  Every placement takes the server out of the empty set;
4. each arrival of the slot still queued goes to the tightest feasible
   server (least residual >= size, lowest index on ties).

"Largest" breaks ties to the lowest virtual-queue index and, within one
queue, to the earliest arrival.  A job's duration is fixed at arrival.

The module gives a path what it asks of a reference: ``sweep`` (one
cluster's key-driven streams), ``replay`` (a raw trace) and
``slot_bytes`` (the algorithmic bytes of one slot step, for the roofline).
"""
from __future__ import annotations

from bisect import bisect_left, bisect_right

import numpy as np

RES = 1 << 16
_SEQ_BITS = 40
_SEQ_MASK = (1 << _SEQ_BITS) - 1


def to_grid(sizes) -> np.ndarray:
    """Sizes in (0, 1] to integer grid units, at least 1."""
    return np.maximum(np.rint(np.asarray(sizes, np.float64) * RES), 1
                      ).astype(np.int64)


def vq_type(g: int, J: int) -> int:
    """Partition-I type of a grid size: I_{2m} = (2/3 2^-m, 2^-m],
    I_{2m+1} = (1/2 2^-m, 2/3 2^-m], sizes <= 2^-J in type 2J - 1."""
    if g <= RES >> J:
        return 2 * J - 1
    m = 0
    while m < J - 1 and g <= RES >> (m + 1):
        m += 1
    return 2 * m if 3 * g > 2 * (RES >> m) else 2 * m + 1


def k_red(J: int) -> np.ndarray:
    """The reduced configuration set K_RED^(J), paper Eq. 7."""
    rows = []
    n = 2 * J
    for m in range(J):
        rows.append({2 * m: 1 << m})
    for m in range(1, J):
        rows.append({2 * m + 1: 3 * (1 << (m - 1))})
    for m in range(2, J):
        rows.append({1: 1, 2 * m: (1 << m) // 3})
    for m in range(1, J):
        rows.append({1: 1, 2 * m + 1: 1 << (m - 1)})
    out = np.zeros((len(rows), n), np.int64)
    for i, row in enumerate(rows):
        for j, v in row.items():
            out[i, j] = v
    return out


class VQSBF:
    """One cluster of ``L`` unit servers under VQS-BF with parameter J."""

    def __init__(self, L: int, J: int):
        self.L, self.J = L, J
        self.kred = k_red(J)
        self.queues: list[list[int]] = [[] for _ in range(2 * J)]
        self.dur_of: dict[int, int] = {}
        self.resid = np.full(L, RES, np.int64)
        self.jobs: list[dict[int, tuple[int, int]]] = [{} for _ in range(L)]
        self.leave: dict[int, list[tuple[int, int]]] = {}
        self.k1 = [False] * L
        self.jstar = [-1] * L
        self.kstar = [0] * L
        self.has_cfg = [False] * L
        self.empty: set[int] = set(range(L))
        self.want: list[set[int]] = [set() for _ in range(2 * J)]
        self.seq = 0
        self.departed = 0
        self.queued = 0
        self._renewal = None      # (queue sizes, config) of the last renewal

    # -- virtual queues: sorted keys (eff << 40 | arrival sequence) --------
    def _pop_leq(self, vq: int, cap: int) -> int | None:
        q = self.queues[vq]
        i = bisect_right(q, (cap << _SEQ_BITS) | _SEQ_MASK)
        if i == 0:
            return None
        eff = q[i - 1] >> _SEQ_BITS
        key = q.pop(bisect_left(q, eff << _SEQ_BITS))
        self.queued -= 1
        return key

    def _largest_leq(self, vq: int, cap: int) -> int:
        q = self.queues[vq]
        i = bisect_right(q, (cap << _SEQ_BITS) | _SEQ_MASK)
        return q[i - 1] >> _SEQ_BITS if i else -1

    def _pop_leq_any(self, cap: int) -> tuple[int, int] | None:
        if not self.queued:
            return None
        best_vq, best = -1, -1
        for vq in range(2 * self.J):
            e = self._largest_leq(vq, cap)
            if e > best:
                best, best_vq = e, vq
        if best_vq < 0:
            return None
        return best_vq, self._pop_leq(best_vq, cap)

    # -- servers -------------------------------------------------------------
    def _config(self) -> tuple[bool, int, int]:
        """(k_1 > 0, j*, k_{j*}) of the max-weight row over the queue
        sizes (first row on ties)."""
        sizes = tuple(len(q) for q in self.queues)
        if self._renewal is None or self._renewal[0] != sizes:
            row = self.kred[int(np.argmax(self.kred @ np.array(sizes)))]
            others = [j for j in np.flatnonzero(row) if j != 1]
            cfg = (bool(row[1] > 0), int(others[0]) if others else -1,
                   int(row[others[0]]) if others else 0)
            self._renewal = (sizes, cfg)
        return self._renewal[1]

    def _renew(self, s: int) -> None:
        self.k1[s], self.jstar[s], self.kstar[s] = self._config()
        self.has_cfg[s] = True

    def _place(self, t: int, s: int, vq: int, key: int) -> None:
        eff, seq = key >> _SEQ_BITS, key & _SEQ_MASK
        if eff > self.resid[s]:
            raise RuntimeError(f"capacity violated on server {s}")
        self.resid[s] -= eff
        self.jobs[s][seq] = (eff, vq)
        self.leave.setdefault(t + self.dur_of.pop(seq), []).append((s, seq))
        self.empty.discard(s)

    def _serve(self, t: int, s: int) -> None:
        if not self.has_cfg[s]:
            self._renew(s)
        jstar, kstar = self.jstar[s], self.kstar[s]
        if self.k1[s] and not any(v == 1 for _, v in self.jobs[s].values()):
            key = self._pop_leq(1, int(self.resid[s]))
            if key is not None:
                self._place(t, s, 1, key)
            elif not self.queues[1]:
                self.want[1].add(s)
        if jstar >= 0:
            count = sum(1 for _, v in self.jobs[s].values() if v == jstar)
            while count < kstar:
                key = self._pop_leq(jstar, int(self.resid[s]))
                if key is None:
                    if not self.queues[jstar]:
                        self.want[jstar].add(s)
                    break
                self._place(t, s, jstar, key)
                count += 1
        while True:
            got = self._pop_leq_any(int(self.resid[s]))
            if got is None:
                break
            self._place(t, s, *got)

    def _visit_idle(self, servers) -> None:
        """Visit ``servers`` while no job is queued: nothing can be placed,
        so their order does not matter.  An empty server renews to the
        configuration of an all-empty queue vector, joins the empty set
        and, like every server, subscribes to the queues it wants."""
        cfg = self._config()
        for s in servers:
            if not self.jobs[s]:
                self.k1[s], self.jstar[s], self.kstar[s] = cfg
                self.has_cfg[s] = True
                self.empty.add(s)
            elif not self.has_cfg[s]:
                self._renew(s)
            if self.k1[s] and not any(v == 1 for _, v in
                                      self.jobs[s].values()):
                self.want[1].add(s)
            if self.jstar[s] >= 0 and self.kstar[s] > sum(
                    1 for _, v in self.jobs[s].values() if v == self.jstar[s]):
                self.want[self.jstar[s]].add(s)

    # -- one slot ------------------------------------------------------------
    def step(self, t: int, sizes_grid, durs) -> tuple[int, int, int]:
        """Advance slot ``t`` with the slot's arrivals (grid sizes and
        durations, in arrival order).  Returns (queue length, occupied
        grid units, departures)."""
        freed, n_dep = set(), 0
        for s, seq in self.leave.pop(t, ()):
            eff, _ = self.jobs[s].pop(seq)
            self.resid[s] += eff
            freed.add(s)
            n_dep += 1
        self.departed += n_dep
        emptied = {s for s in freed if not self.jobs[s]}

        arrivals, woken = [], set()
        for g, d in zip(sizes_grid, durs):
            g = int(g)
            vq = vq_type(g, self.J)
            eff = max(g, RES >> self.J) if vq == 2 * self.J - 1 else g
            key = (eff << _SEQ_BITS) | self.seq
            self.dur_of[self.seq] = max(int(d), 1)
            self.seq += 1
            q = self.queues[vq]
            q.insert(bisect_right(q, key), key)
            self.queued += 1
            arrivals.append((vq, key))
            woken |= self.want[vq]
            self.want[vq] = set()

        visit = freed | emptied | woken
        if self.queued and self.empty:
            visit |= self.empty
        order = sorted(visit)
        for i, s in enumerate(order):
            if not self.queued:
                self._visit_idle(order[i:])
                break
            if not self.jobs[s]:
                self._renew(s)
                self.empty.add(s)
            self._serve(t, s)

        for vq, key in arrivals:
            eff = key >> _SEQ_BITS
            fit = np.flatnonzero(self.resid >= eff)
            if not len(fit):
                continue
            q = self.queues[vq]
            i = bisect_left(q, key)
            if i < len(q) and q[i] == key:
                s = int(fit[np.argmin(self.resid[fit])])
                q.pop(i)
                self.queued -= 1
                self._place(t, s, vq, key)
        occ = int(self.L * RES - self.resid.sum())
        return self.queued, occ, n_dep


def simulate(n, sizes, durs, *, L: int, J: int, slots: int | None = None
             ) -> dict[str, np.ndarray]:
    """Run VQS-BF over per-slot arrival arrays: ``n[t]`` arrivals in slot
    ``t`` with float sizes ``sizes[t, :n[t]]`` and durations
    ``durs[t, :n[t]]``.  Returns per-slot ``queue_len``, ``occupancy`` (as
    float32 servers, exact grid total over 2**16) and cumulative
    ``departed``."""
    T = len(n) if slots is None else slots
    sim = VQSBF(L, J)
    q = np.zeros(T, np.int64)
    occ = np.zeros(T, np.int64)
    dep = np.zeros(T, np.int64)
    for t in range(T):
        k = int(n[t])
        q[t], occ[t], _ = sim.step(t, to_grid(sizes[t, :k]), durs[t, :k])
        dep[t] = sim.departed
    return {"queue_len": q,
            "occupancy": occ.astype(np.float32) / np.float32(RES),
            "departed": dep}


def sweep(streams, sizes: dict) -> dict[str, np.ndarray]:
    """The reference over one cluster's streams ``(n, sizes, durs)``: an
    arrival in lane ``a`` of a slot keeps the duration in the last
    ``A_max`` columns of ``durs``."""
    n, sz, durs = (np.asarray(x) for x in streams)
    return simulate(n, sz, durs[:, -sz.shape[1]:], L=sizes["L"],
                    J=sizes["J"])


def replay(tr: dict, slots: int, sizes: dict) -> dict[str, np.ndarray]:
    """The reference over the raw trace's first ``slots`` slots, each
    slot's arrivals in trace order."""
    keep = tr["arrival_slots"] < slots
    at = tr["arrival_slots"][keep]
    counts = np.bincount(at, minlength=slots)
    A = max(int(counts.max()), 1)
    lane = np.arange(len(at)) - np.repeat(np.cumsum(counts) - counts, counts)
    sz = np.zeros((slots, A))
    du = np.ones((slots, A), np.int64)
    sz[at, lane] = tr["size"][keep]
    du[at, lane] = tr["durations"][keep]
    return simulate(counts, sz, du, L=sizes["L"], J=sizes["J"])


WORD = 4


def slot_bytes(sizes: dict) -> int:
    """Bytes one member-slot of VQS-BF must move, whatever engine runs it:
    the ``L x K`` job-slot sizes and remaining durations, the ``L`` server
    residuals and the ``2J x Qcap`` ring entries (effective size, duration,
    arrival stamp), all int32, read and written once; and the slot's
    ``A_max`` arrivals (a size and a duration each) read."""
    L, K, J = sizes["L"], sizes["K"], sizes["J"]
    state = 2 * L * K + L + 3 * (2 * J) * sizes["Qcap"]
    return 2 * WORD * state + 2 * WORD * sizes["A_max"]
