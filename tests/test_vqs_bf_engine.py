"""VQS-BF accelerator engines + the admission-mode dispatch bugfix.

Covers the ISSUE 9 acceptance paths: bit-parity of scan/pallas with the
event-driven ``core/vqs_bf.py`` oracle on synthetic traces AND the
google_like_50 CSV fixture, scan-vs-reference equivalence on random
streams (fault planes included), the paper's Section VI delay claim
(VQS-BF tail well below VQS tail on shared streams), chunked/state
threading, capacity planning via ``estimate_capacity(policy="vqs-bf")``
and the ``AdmissionController.policy`` dispatch (all three documented
modes distinct + unknown value raises)."""
import os

import jax
import numpy as np
import pytest

from repro.cluster.admission import AdmissionController, PendingJob
from repro.core import VQSBF, load_trace_csv, simulate_trace
from repro.core.engine import (make_streams, run_policy, run_policy_streams,
                               streams_from_trace, Workload)
from repro.core.engine.vqs_bf import (_run_vqs_bf_reference_streams,
                                      run_vqs_bf_streams, run_vqs_bf_trace)
from repro.kernels.vqs_bf.vqs_bf import vqs_bf_pallas

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "google_like_50.csv")

# vqs-bf serves ONE placement per work step (largest-fit pops depend on
# the residual the previous pop left), so the bound is sized to the
# per-slot burst, not to A_max
WORK = 64


def _random_trace(seed, T, N, grid=64):
    rng = np.random.default_rng(seed)
    slots = np.sort(rng.integers(0, T, N))
    sizes = rng.integers(1, grid, N) / float(grid)
    durs = rng.integers(1, 60, N)
    return slots, sizes, durs


def _uniform_sampler(lo, hi):
    def sampler(key, n):
        return jax.random.uniform(key, (n,), minval=lo, maxval=hi)
    return sampler


# ---------------------------------------------------------------------------
# trace-driven parity with the event-driven engine (the oracle bridge)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("engine", ["reference", "scan", "pallas"])
@pytest.mark.parametrize("seed,J,L", [(0, 3, 5), (7, 5, 12), (3, 2, 1)])
def test_vqs_bf_engine_bitmatches_numpy_on_trace(engine, seed, J, L):
    """run_policy_streams(policy="vqs-bf") == simulate_trace(VQSBF(J))
    queue trajectory, slot for slot, on grid-sized jobs."""
    T, N = 400, 60 * L
    slots, sizes, durs = _random_trace(seed, T, N)
    ref = simulate_trace(VQSBF(J=J), L=L, arrival_slots=slots, sizes=sizes,
                         durations=durs, horizon=T, seed=0, record_every=1)
    st = streams_from_trace(slots, sizes, durs, horizon=T)
    res = run_policy_streams(st, policy="vqs-bf", engine=engine, J=J, L=L,
                             K=1 << J, Qcap=2048,
                             A_max=int(st.sizes.shape[1]), work_steps=WORK)
    assert int(res.truncated) == 0
    assert int(res.dropped) == 0
    np.testing.assert_array_equal(np.asarray(res.queue_len),
                                  ref.queue_lens)
    assert int(res.departed[-1]) == ref.departed


@pytest.mark.parametrize("engine", ["scan", "pallas"])
def test_vqs_bf_google50_trace_bitmatches_numpy(engine):
    """The collapsed google_like_50 fixture replays through the
    accelerated engines and reproduces the numpy oracle exactly."""
    trace = load_trace_csv(FIXTURE, slot_seconds=10.0)
    sizes = np.maximum(trace.cpu, trace.mem)
    T = int(trace.arrival_slots[-1]) + 80
    ref = simulate_trace(VQSBF(J=3), L=8, arrival_slots=trace.arrival_slots,
                         sizes=sizes, durations=trace.durations, horizon=T,
                         seed=0, record_every=1)
    st = streams_from_trace(trace, horizon=T)
    res = run_policy_streams(st, policy="vqs-bf", engine=engine, J=3, L=8,
                             K=8, Qcap=256, A_max=int(st.sizes.shape[1]),
                             work_steps=WORK)
    assert int(res.truncated) == 0 and int(res.dropped) == 0
    np.testing.assert_array_equal(np.asarray(res.queue_len),
                                  ref.queue_lens)
    assert int(res.departed[-1]) == ref.departed > 0


# ---------------------------------------------------------------------------
# scan vs reference on random streams (fault planes included)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed,lam,J,fault_rate",
                         [(0, 0.3, 2, 0.0), (1, 1.0, 4, 0.0),
                          (4, 1.2, 3, 0.02)])
def test_vqs_bf_scan_bitmatches_reference_engine(seed, lam, J, fault_rate):
    st = make_streams(jax.random.PRNGKey(seed), lam, 0.02,
                      _uniform_sampler(0.05, 0.9), L=6, K=40, A_max=6,
                      horizon=600, fault_rate=fault_rate,
                      repair_rate=0.2 if fault_rate else 1.0)
    kw = dict(J=J, L=6, K=40, Qcap=512, A_max=6)
    ref = _run_vqs_bf_reference_streams(st, **kw)
    scn = run_vqs_bf_streams(st, work_steps=WORK, **kw)
    assert int(scn.truncated) == 0
    for field in ("queue_len", "occupancy", "departed", "dropped",
                  "preempted", "requeued", "lost"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, field)),
                                      np.asarray(getattr(scn, field)))
    if fault_rate:
        assert int(ref.preempted) > 0
        assert int(ref.preempted) == int(ref.requeued) + int(ref.lost)


def test_vqs_bf_truncation_counted_not_silent():
    """A starved work bound must report itself via ``truncated``."""
    st = make_streams(jax.random.PRNGKey(2), 3.0, 0.01,
                      _uniform_sampler(0.05, 0.3), L=8, K=32, A_max=8,
                      horizon=300)
    res = run_vqs_bf_streams(st, J=3, L=8, K=32, Qcap=512, A_max=8,
                             work_steps=1)
    assert int(res.truncated) > 0


@pytest.mark.parametrize("work_steps", [1, 2, 4])
def test_vqs_bf_kernel_matches_scan_under_starved_bound(work_steps):
    """The kernel's early-exit work list stops at the scan engine's bound
    (``n_steps <= W``): a starved bound truncates the same slots and
    leaves the same trajectory on both engines."""
    st = make_streams(jax.random.PRNGKey(2), 3.0, 0.01,
                      _uniform_sampler(0.05, 0.3), L=8, K=32, A_max=8,
                      horizon=300)
    kw = dict(J=3, L=8, K=32, Qcap=512, A_max=8, work_steps=work_steps)
    scn = run_vqs_bf_streams(st, **kw)
    krn = run_vqs_bf_trace(st, engine="pallas", strict=True, **kw)
    assert int(scn.truncated) > 0
    for field in ("queue_len", "occupancy", "departed", "dropped",
                  "truncated"):
        np.testing.assert_array_equal(np.asarray(getattr(scn, field)),
                                      np.asarray(getattr(krn, field)))


def test_vqs_bf_kernel_step_counter_shows_early_exit():
    """``steps`` counts the work steps the kernel ran: the same count and
    trajectory under any bound that never truncates, at least one step
    a slot, below the bound, and at most two a slot under ``W = 1``."""
    st = make_streams(jax.random.PRNGKey(1), 1.0, 0.02,
                      _uniform_sampler(0.05, 0.9), L=6, K=40, A_max=6,
                      horizon=300)
    T = int(st.n.shape[0])
    kw = dict(J=4, L=6, K=40, Qcap=512, A_max=6, interpret=True)

    def run(work_steps):
        return vqs_bf_pallas(st.n[None], st.sizes[None], st.durs[None],
                             work_steps=work_steps, **kw)

    out = {w: [np.asarray(x) for x in run(w)] for w in (1, 32, 64)}
    *traj32, trunc32, steps32 = out[32]
    *traj64, trunc64, steps64 = out[64]
    assert int(trunc32[0]) == 0 and int(trunc64[0]) == 0
    for a, b in zip(traj32, traj64):
        np.testing.assert_array_equal(a, b)
    assert int(steps32[0]) == int(steps64[0])
    assert T <= int(steps32[0]) < T * (32 + 1)
    assert T <= int(out[1][-1][0]) <= 2 * T


# ---------------------------------------------------------------------------
# the kernel keeps its servers on lanes: parity where L spans lane tiles
# ---------------------------------------------------------------------------
class _CountingVQSBF(VQSBF):
    """The event-driven VQS-BF, counting the placements its serve pass
    makes (the work list's; the closing BF-J pass is not counted)."""

    def bind(self, *args, **kwargs):
        super().bind(*args, **kwargs)
        self.serve_placements = 0
        self._serving = False

    def _serve(self, t, server):
        self._serving = True
        super()._serve(t, server)
        self._serving = False

    def _place(self, t, server, job):
        self.serve_placements += self._serving
        super()._place(t, server, job)


@pytest.fixture(scope="module", params=[12, 200], ids=lambda L: f"L{L}")
def lane_tile_run(request):
    """One trace near the knee through the event-driven oracle, the scan
    engine and the kernel (interpret mode).  L = 200 spans two 128-lane
    tiles and is not a multiple of 128; sizes on a 1/8 grid make equal
    residuals common, so the BF-J pass's lowest-index tie-break decides
    between servers on both sides of the tile boundary.  The oracle also
    counts those decisions and its serve-pass placements."""
    from repro.core.cluster_state import Cluster

    L, T, J, K, Qcap = request.param, 300, 4, 16, 512
    # just below the knee: 1.8 jobs of mean size 1/2 offered a server,
    # mean duration 30 slots (the queue grows from ~1.9)
    slots, sizes, durs = _random_trace(L, T, int(1.8 * L / 30 * T), grid=8)
    crossings = []
    tightest = Cluster.tightest_feasible

    def spy(cl, size):
        s = tightest(cl, size)
        if s >= 0:
            tied = cl._by_resid[int(cl.residual[s])]
            crossings.append(min(tied) < 128 <= max(tied))
        return s

    oracle = _CountingVQSBF(J=J)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Cluster, "tightest_feasible", spy)
        ref = simulate_trace(oracle, L=L, arrival_slots=slots, sizes=sizes,
                             durations=durs, horizon=T, seed=0,
                             record_every=1)
    st = streams_from_trace(slots, sizes, durs, horizon=T)
    kw = dict(J=J, L=L, K=K, Qcap=Qcap, A_max=int(st.sizes.shape[1]),
              work_steps=WORK)
    scn = run_vqs_bf_streams(st, **kw)
    krn = [np.asarray(x[0]) for x in vqs_bf_pallas(
        st.n[None], st.sizes[None], st.durs[None], interpret=True, **kw)]
    return dict(L=L, T=T, ref=ref, oracle=oracle, crossings=crossings,
                scan=scn, kernel=krn)


def test_vqs_bf_kernel_bitmatches_scan_across_lane_tiles(lane_tile_run):
    """Kernel == scan == oracle, slot for slot, at an L that is not a
    multiple of 128 — and at L = 200 ties across the 128-server boundary
    decided placements."""
    r = lane_tile_run
    scn = r["scan"]
    qlen, occ, ndep, dropped, trunc, _ = r["kernel"]
    assert int(dropped) == int(scn.dropped) == 0
    assert int(trunc) == int(scn.truncated) == 0
    np.testing.assert_array_equal(qlen, np.asarray(scn.queue_len))
    np.testing.assert_array_equal(occ, np.asarray(scn.occupancy))
    np.testing.assert_array_equal(np.cumsum(ndep), np.asarray(scn.departed))
    np.testing.assert_array_equal(qlen, r["ref"].queue_lens)
    assert qlen.max() > 0                  # near the knee: a real queue
    if r["L"] > 128:
        assert sum(r["crossings"]) > 0
        assert occ.max() > 128             # servers past the tile are busy


def test_vqs_bf_kernel_steps_equal_scan_early_exit(lane_tile_run):
    """The scan engine's early exit runs one work step a work-list
    placement plus the closing step with no placer; the kernel runs that
    closing step also when nothing was visited.  So the kernel's ``steps``
    is T plus the serve-pass placements of the oracle both engines
    bit-match."""
    r = lane_tile_run
    steps = int(r["kernel"][-1])
    assert r["oracle"].serve_placements > 0
    assert steps == r["T"] + r["oracle"].serve_placements


# ---------------------------------------------------------------------------
# the paper's Section VI claim: VQS throughput, BF-like delay
# ---------------------------------------------------------------------------
def test_vqs_bf_tail_well_below_vqs_tail_on_shared_streams():
    """Same pre-generated streams, stable load: VQS-BF's backfilled queue
    sits far below plain VQS's (the Theorem 4 delay motivation)."""
    st = make_streams(jax.random.PRNGKey(3), 0.3, 0.05,
                      _uniform_sampler(0.05, 0.9), L=6, K=40, A_max=6,
                      horizon=1000)
    kw = dict(J=3, L=6, K=40, Qcap=2048, A_max=6)
    vqs = run_policy_streams(st, policy="vqs", engine="scan", **kw)
    vqsbf = run_policy_streams(st, policy="vqs-bf", engine="scan",
                               work_steps=WORK, **kw)
    assert int(vqs.truncated) == 0 and int(vqsbf.truncated) == 0
    tail_vqs = float(np.mean(np.asarray(vqs.queue_len)[200:]))
    tail_bf = float(np.mean(np.asarray(vqsbf.queue_len)[200:]))
    assert tail_bf < 0.6 * tail_vqs
    assert int(np.asarray(vqsbf.queue_len).max()) \
        <= int(np.asarray(vqs.queue_len).max())


# ---------------------------------------------------------------------------
# stack inheritance: chunked state threading + capacity planning
# ---------------------------------------------------------------------------
def test_vqs_bf_chunked_sweep_bitmatches_one_shot(tmp_path):
    st = make_streams(jax.random.PRNGKey(5), 1.0, 0.05,
                      _uniform_sampler(0.05, 0.9), L=4, K=8, A_max=4,
                      horizon=240)
    kw = dict(J=3, L=4, K=8, Qcap=64, A_max=4, work_steps=32)
    one = run_policy_streams(st, policy="vqs-bf", engine="scan", **kw)
    chk = run_policy_streams(st, policy="vqs-bf", engine="scan", chunk=60,
                             checkpoint_dir=str(tmp_path), **kw)
    for field in ("queue_len", "occupancy", "departed", "dropped",
                  "truncated"):
        np.testing.assert_array_equal(np.asarray(getattr(one, field)),
                                      np.asarray(getattr(chk, field)))


def test_estimate_capacity_accepts_vqs_bf():
    from repro.serving.engine import estimate_capacity
    out = estimate_capacity(4, 1.0, 20.0, _uniform_sampler(0.05, 0.9),
                            ensembles=4, horizon=200, policy="vqs-bf",
                            J=3, K=8, Qcap=64, A_max=4, work_steps=32)
    assert out["policy"] == "vqs-bf"
    assert out["truncated"] == 0
    assert out["slots_simulated"] == 4 * 200


# ---------------------------------------------------------------------------
# the bugfix: AdmissionController dispatches on its policy field
# ---------------------------------------------------------------------------
def _crafted_refill(policy):
    """Fill one replica, queue a crafted mix, free it, serve the queue."""
    ac = AdmissionController(1, policy=policy, J=3)
    big = PendingJob(0, 1.0)
    assert ac.admit([big]) == [(0, 0)]
    ac.admit([PendingJob(1, 0.9), PendingJob(2, 0.45), PendingJob(3, 0.30),
              PendingJob(4, 0.28), PendingJob(5, 0.26),
              PendingJob(6, 0.10)])
    ac.release(0, big.size)
    return [rid for rid, _ in ac.refill(0)]


def test_admission_policy_modes_dispatch_differently():
    bf = _crafted_refill("bf")
    vqsbf = _crafted_refill("vqs-bf")
    fifo = _crafted_refill("fifo")
    # bf grabs the largest fitting request first
    assert bf[0] == 1
    # fifo serves the head and then blocks on the 0.9 head-of-line gap
    assert fifo == [1]
    # vqs-bf follows its max-weight configuration, not pure size greed
    assert vqsbf != bf
    assert vqsbf != fifo


def test_admission_unknown_policy_raises():
    with pytest.raises(ValueError, match="bf, vqs-bf, fifo"):
        AdmissionController(2, policy="typo")


def test_admission_vqs_bf_renews_config_at_empty_epochs():
    ac = AdmissionController(1, policy="vqs-bf", J=3)
    assert ac._active_cfg[0] is None
    job = PendingJob(0, 0.9)
    ac.admit([job])                      # replica busy, nothing queued
    ac.admit([PendingJob(1, 0.45)])      # doesn't fit -> queues
    ac.release(0, job.size)              # replica empties
    placed = ac.refill(0)                # renewal happens here
    assert ac._active_cfg[0] is not None
    assert np.asarray(ac._active_cfg[0]).sum() > 0   # a K_RED row
    assert (1, 0) in placed


def test_admission_bf_mode_unchanged_by_dispatch():
    """policy="bf" keeps the exact legacy BF-S behaviour (largest fitting
    first, FIFO among equal sizes)."""
    ac = AdmissionController(1, policy="bf", J=3)
    big = PendingJob(0, 1.0)
    ac.admit([big])
    ac.admit([PendingJob(1, 0.5), PendingJob(2, 0.5), PendingJob(3, 0.4)])
    ac.release(0, big.size)
    assert [rid for rid, _ in ac.refill(0)] == [1, 2]
