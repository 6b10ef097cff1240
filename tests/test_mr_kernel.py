"""Property-based parity: the fused bfjs-mr Pallas kernel (interpret mode)
vs the scan engine on hypothesis-generated workloads.

Random ``(lam, mu, R, capacity, Qcap)`` draws build real stream ensembles
and assert BIT-EXACT occupancy/queue/departure trajectories between
``kernels/bfjs_mr`` and ``run_bfjs_mr_streams`` — plus ``truncated == 0``
under the deliberately conservative bounds (ample K and work list), so the
bit-match contract extends through the scan engine to the event-driven
oracle.  Settings are derandomized and bounded (CI pins
``--hypothesis-seed=0`` on top), so tier-1 stays deterministic."""
import numpy as np
import pytest

# deselected by the fast tier-1 lane (-m "not slow"); CI runs
# the full suite
pytestmark = pytest.mark.slow

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

import jax

from repro.core.engine import SchedStreams, make_streams, streams_from_trace
from repro.core.engine.bfjs_mr import run_bfjs_mr_streams
from repro.kernels.bfjs_mr.ops import bfjs_mr_simulate
from repro.kernels.common import LANES

#: bounded deterministic profile — a handful of examples is enough because
#: every example is itself a (G=2) x 80-slot trajectory sweep.
MR_SETTINGS = settings(max_examples=12, deadline=None, derandomize=True)


def _sampler(R, hi):
    def sampler(key, n):
        u = jax.random.uniform(key, (n, R), minval=0.05, maxval=hi)
        return u[:, 0] if R == 1 else u
    return sampler


def _assert_bitmatch(pal, ref):
    # fit_chunks is the kernel's own work counter; the scan engine has none
    for f in (f for f in pal._fields if f != "fit_chunks"):
        np.testing.assert_array_equal(
            np.asarray(getattr(pal, f)), np.asarray(getattr(ref, f)),
            err_msg=f"kernel diverged from the scan engine on {f!r}")


@MR_SETTINGS
@given(data=st.data(),
       R=st.integers(1, 3),
       lam=st.floats(0.1, 1.0),
       mu=st.floats(0.2, 0.9),
       L=st.integers(2, 4),
       A_max=st.integers(2, 4),
       Qcap=st.sampled_from([16, 48]),
       window=st.sampled_from([None, 40]),
       seed=st.integers(0, 2 ** 16))
def test_mr_kernel_bitmatches_scan_on_random_workloads(
        data, R, lam, mu, L, A_max, Qcap, window, seed):
    """Interpret-mode kernel == scan engine, slot by slot, and the
    conservative bounds keep every deviation counter at zero."""
    capacity = tuple(data.draw(st.sampled_from([0.75, 1.0]),
                               label=f"cap[{r}]") for r in range(R))
    K, T, G = 16, 80, 2
    # sizes stay below min(capacity) so the workload is placeable and the
    # ample K/work bounds guarantee truncated == 0 by construction
    keys = jax.random.split(jax.random.PRNGKey(seed), G)
    streams = jax.vmap(lambda k: make_streams(
        k, lam, mu, _sampler(R, 0.6), L=L, K=K, A_max=A_max, horizon=T,
        num_resources=R))(keys)
    kw = dict(L=L, K=K, Qcap=Qcap, A_max=A_max, work_steps=A_max + 8,
              capacity=capacity)
    pal = bfjs_mr_simulate(streams, window=window, **kw)
    ref = bfjs_mr_simulate(streams, use_pallas=False, **kw)
    _assert_bitmatch(pal, ref)
    assert int(np.asarray(pal.truncated).sum()) == 0


@MR_SETTINGS
@given(R=st.integers(1, 3),
       n_jobs=st.integers(1, 60),
       horizon=st.sampled_from([40, 80]),
       seed=st.integers(0, 2 ** 16))
def test_mr_kernel_bitmatches_scan_on_random_traces(R, n_jobs, horizon,
                                                    seed):
    """Trace-built streams (per-arrival duration lanes only, the
    streams_from_trace layout) replay identically through kernel and scan
    engine — including the D = A_max duration-stream shape."""
    rng = np.random.default_rng(seed)
    slots = rng.integers(0, horizon, n_jobs)
    sizes = rng.integers(1, int(0.7 * 64), (n_jobs, R)) / 64.0
    durs = rng.integers(1, 20, n_jobs)
    streams = streams_from_trace(slots, sizes if R > 1 else sizes[:, 0],
                                 durs, horizon=horizon, num_resources=R)
    A_max = int(streams.sizes.shape[1])
    batched = jax.tree.map(lambda x: x[None], streams)
    kw = dict(L=3, K=16, Qcap=64, A_max=A_max, work_steps=A_max + 8,
              capacity=(1.0,) * R)
    pal = bfjs_mr_simulate(batched, **kw)
    ref = bfjs_mr_simulate(batched, use_pallas=False, **kw)
    _assert_bitmatch(pal, ref)
    assert int(np.asarray(pal.truncated).sum()) == 0


# ---------------------------------------------------------------------------
# the live-prefix bound of the BF-S fit search
# ---------------------------------------------------------------------------
#: queue places: four CH = LANES chunks of the fit search
BURST_QCAP = 512


@pytest.fixture(scope="module")
def burst():
    """A trace whose queue climbs past two chunks (6 arrivals a slot for
    60 slots on 4 servers), then drains for 180 slots under a trickle of
    late arrivals.  BF-S takes the largest fitting job wherever it sits,
    so the drain leaves live jobs in the third chunk above holes at low
    places, where the late arrivals land.  Returns the streams, the
    engine arguments, the kernel's run and the scan engine's."""
    rng = np.random.default_rng(0)
    T, peak, rate = 240, 60, 6
    slots = np.concatenate([np.repeat(np.arange(peak), rate),
                            np.sort(rng.integers(peak, T, 40))])
    sizes = rng.integers(7, 39, (len(slots), 2)) / 64.0
    durs = rng.integers(2, 12, len(slots))
    streams = streams_from_trace(slots, sizes, durs, horizon=T,
                                 num_resources=2)
    kw = dict(L=4, K=16, Qcap=BURST_QCAP, A_max=int(streams.sizes.shape[1]),
              work_steps=64, capacity=(1.0, 1.0))
    batched = jax.tree.map(lambda x: x[None], streams)
    pal = bfjs_mr_simulate(batched, window=40, **kw)
    ref = bfjs_mr_simulate(batched, use_pallas=False, **kw)
    return streams, kw, pal, ref


def test_mr_kernel_bitmatches_scan_past_the_first_chunks(burst):
    """Kernel == scan engine on a queue that fills three chunks and then
    holds its last live jobs in the third chunk while fewer than one
    chunk's worth are queued: the bound must reach the top live place,
    not the queue's length."""
    streams, kw, pal, ref = burst
    assert int(np.max(np.asarray(ref.queue_len))) > 2 * LANES
    # non-vacuous: at slot 200 under LANES jobs are queued, but the
    # highest live place lies past 2 * LANES
    sub = jax.tree.map(lambda x: x[:200], streams)
    _, state = run_bfjs_mr_streams(sub, **kw, return_state=True)
    live = np.flatnonzero(np.asarray(state[5]) >= 0)     # qseq >= 0
    assert 0 < len(live) < LANES < 2 * LANES < live.max()
    _assert_bitmatch(pal, ref)
    assert int(pal.truncated[0]) == 0 and int(pal.dropped[0]) == 0


def test_mr_kernel_fit_chunks_follow_the_live_prefix(burst):
    """``fit_chunks`` counts the chunks the fit search ran.  At light load
    (no more than LANES jobs ever queued) every call runs one chunk, but
    those of a slot that starts with an empty queue run none; under the
    burst the calls run more, and never more than Qcap / LANES each.
    The calls are one a work step plus one saturation check a slot."""
    G, T, L, K, A_max = 2, 80, 4, 16, 4
    keys = jax.random.split(jax.random.PRNGKey(3), G)
    light = jax.vmap(lambda k: make_streams(
        k, 0.5, 0.2, _sampler(2, 0.6), L=L, K=K, A_max=A_max, horizon=T,
        num_resources=2))(keys)
    res = bfjs_mr_simulate(light, L=L, K=K, Qcap=BURST_QCAP, A_max=A_max,
                           work_steps=A_max + 8, capacity=(1.0, 1.0))
    n, qlen = np.asarray(light.n), np.asarray(res.queue_len)
    before = np.concatenate([np.zeros((G, 1), int), qlen[:, :-1]], axis=1)
    assert int(np.max(before + n)) <= LANES
    empty = ((before == 0) & (n == 0)).sum(axis=1)
    assert 0 < empty.min() and empty.max() < T
    calls = np.asarray(res.steps) + T
    np.testing.assert_array_equal(np.asarray(res.fit_chunks),
                                  calls - 2 * empty)

    _, _, pal, _ = burst
    calls = int(pal.steps[0]) + int(pal.queue_len.shape[1])
    assert calls < int(pal.fit_chunks[0]) <= calls * BURST_QCAP // LANES
