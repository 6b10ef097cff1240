"""JAX scheduling engine: agreement with the event-driven engine and the
Pallas kernel; Monte-Carlo vmap path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BFJS, PartitionI, RES, ServiceModel, Uniform, \
    simulate, to_grid
from repro.core.jax_sched import (best_fit_place, best_fit_server,
                                  make_streams, max_weight_config_jax,
                                  monte_carlo_bfjs, run_bfjs,
                                  run_bfjs_streams, vq_type_of)
from repro.core.partition import k_red, max_weight_config


def test_best_fit_place_matches_pallas_ref():
    from repro.kernels.best_fit.ref import best_fit_ref
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    resid = jax.random.uniform(k1, (32,))
    sizes = jax.random.uniform(k2, (16,), minval=0.05, maxval=0.7)
    a1, r1 = best_fit_place(resid, sizes)
    a2, r2 = best_fit_ref(resid, sizes)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_allclose(r1, r2, rtol=1e-6)


def test_best_fit_server_rejects():
    assert int(best_fit_server(jnp.array([0.2, 0.1]), jnp.asarray(0.5))) == -1
    assert int(best_fit_server(jnp.array([0.6, 0.5]), jnp.asarray(0.5))) == 1


def test_vq_type_of_matches_partition():
    for J in (2, 4, 6):
        part = PartitionI(J)
        sizes = np.linspace(0.012, 1.0, 97)
        ints = to_grid(sizes)
        expect = part.type_of(ints)
        got = np.asarray(vq_type_of(jnp.asarray(sizes), J))
        agree = (got == expect).mean()
        assert agree > 0.95, (J, agree)  # float/grid boundary slack


@pytest.mark.parametrize("J", [2, 3, 6, 10])
def test_vq_type_of_matches_partition_exactly_on_full_grid(J):
    """Exact parity with PartitionI.type_of_scalar on EVERY grid size,
    including exact powers of two and the size <= 2^-J tail."""
    part = PartitionI(J)
    g = np.arange(1, RES + 1, dtype=np.int64)
    sizes = (g.astype(np.float64) / RES).astype(np.float32)  # exact in f32
    expect = part.type_of(g)
    got = np.asarray(vq_type_of(jnp.asarray(sizes), J))
    np.testing.assert_array_equal(got, expect)
    # spot-check the scalar API on the boundaries the float path fudged
    for m in range(J):
        assert int(vq_type_of(jnp.float32(2.0 ** -m), J)) \
            == part.type_of_scalar(RES >> m)
    assert int(vq_type_of(jnp.float32(2.0 ** -J), J)) == 2 * J - 1


def test_max_weight_config_jax_matches_numpy():
    for J in (2, 4):
        q = np.random.default_rng(0).integers(0, 100, size=2 * J)
        i_np, c_np = max_weight_config(J, q)
        i_j, c_j = max_weight_config_jax(J, jnp.asarray(q))
        w = k_red(J) @ q
        assert w[int(i_j)] == w.max()
        np.testing.assert_array_equal(np.asarray(c_j), k_red(J)[int(i_j)])


def test_run_bfjs_stable_vs_overloaded():
    def sampler(key, n):
        return jax.random.uniform(key, (n,), minval=0.1, maxval=0.9)

    stable = run_bfjs(jax.random.PRNGKey(0), lam=0.06, mu=0.01,
                      sampler=sampler, L=5, K=12, Qcap=512, A_max=6,
                      horizon=15_000)
    over = run_bfjs(jax.random.PRNGKey(0), lam=0.25, mu=0.01,
                    sampler=sampler, L=5, K=12, Qcap=512, A_max=6,
                    horizon=15_000)
    q_s = float(stable.queue_len[-3000:].mean())
    q_o = float(over.queue_len[-3000:].mean())
    assert q_s < 30
    assert q_o > 5 * q_s       # overloaded queue blows up
    assert int(stable.dropped) == 0


def _uniform_sampler(lo, hi):
    def sampler(key, n):
        return jax.random.uniform(key, (n,), minval=lo, maxval=hi)
    return sampler


@pytest.mark.parametrize("seed,lam", [(0, 0.5), (1, 1.5), (2, 3.0)])
def test_scan_engine_bitmatches_reference_engine(seed, lam):
    """The branch-free engine on pre-generated streams reproduces the seed
    nested-loop engine trajectory bit-for-bit (same key)."""
    sampler = _uniform_sampler(0.05, 0.5)
    kw = dict(L=6, K=8, Qcap=64, A_max=6, horizon=800)
    ref = run_bfjs(jax.random.PRNGKey(seed), lam, 0.02, sampler,
                   engine="reference", **kw)
    new = run_bfjs(jax.random.PRNGKey(seed), lam, 0.02, sampler,
                   engine="scan", **kw)
    assert int(new.truncated) == 0
    np.testing.assert_array_equal(np.asarray(new.queue_len),
                                  np.asarray(ref.queue_len))
    np.testing.assert_array_equal(np.asarray(new.departed),
                                  np.asarray(ref.departed))
    np.testing.assert_array_equal(np.asarray(new.occupancy),
                                  np.asarray(ref.occupancy))
    assert int(new.dropped) == int(ref.dropped)


def test_streams_bitmatch_reference_inloop_draws():
    """make_streams replays the reference engine's exact per-slot key chain:
    batched pre-generation == the in-loop draws, bitwise."""
    lam, mu, L, K, A_max, T = 1.5, 0.01, 4, 6, 8, 60
    sampler = _uniform_sampler(0.05, 0.5)
    key = jax.random.PRNGKey(42)
    st = make_streams(key, lam, mu, sampler, L=L, K=K, A_max=A_max,
                      horizon=T)
    from repro.core.jax_sched import _geometric
    k = key
    for t in range(T):
        k, _, k_n, k_sizes, k_dur = jax.random.split(k, 5)
        n = jnp.minimum(jax.random.poisson(k_n, lam), A_max)
        assert int(st.n[t]) == int(n)
        np.testing.assert_array_equal(np.asarray(st.sizes[t]),
                                      np.asarray(sampler(k_sizes, A_max)))
        np.testing.assert_array_equal(
            np.asarray(st.durs[t]),
            np.asarray(_geometric(k_dur, mu, (L * K + A_max,))))


def test_streams_are_the_same_eager_and_inside_jit():
    """A key gives the same streams drawn eagerly and inside a jitted
    program.  At this size (4.8M duration draws) dividing by a
    device-side ``log1p(-mu)`` flipped a few ``ceil``s between the two."""
    from repro.core.engine import make_streams
    sampler = _uniform_sampler(0.1, 0.6)
    kw = dict(L=1000, K=16, A_max=64, horizon=300)
    key = jax.random.PRNGKey(0)
    eager = make_streams(key, 25.0, 0.01, sampler, **kw)
    jitted = jax.jit(lambda k: make_streams(k, 25.0, 0.01, sampler,
                                            **kw))(key)
    for f in ("n", "sizes", "durs"):
        np.testing.assert_array_equal(np.asarray(getattr(eager, f)),
                                      np.asarray(getattr(jitted, f)))


def test_scan_engine_truncation_is_flagged_not_silent():
    """A too-small work list must be reported via `truncated`, and a
    sufficient one must reproduce the reference exactly."""
    sampler = _uniform_sampler(0.05, 0.2)   # many small jobs per server
    kw = dict(L=4, K=12, Qcap=64, A_max=8)
    streams = make_streams(jax.random.PRNGKey(5), 4.0, 0.05, sampler,
                           L=4, K=12, A_max=8, horizon=400)
    tiny = run_bfjs_streams(streams, Qcap=64, L=4, K=12, A_max=8,
                            work_steps=1)
    ample = run_bfjs_streams(streams, Qcap=64, L=4, K=12, A_max=8,
                             work_steps=24)
    assert int(tiny.truncated) > 0
    assert int(ample.truncated) == 0
    ref = run_bfjs(jax.random.PRNGKey(5), 4.0, 0.05, sampler,
                   engine="reference", horizon=400, **kw)
    np.testing.assert_array_equal(np.asarray(ample.queue_len),
                                  np.asarray(ref.queue_len))


def test_monte_carlo_engines_agree():
    """vmapped scan engine == gridded Pallas kernel (interpret) == reference,
    member by member, on shared streams."""
    sampler = _uniform_sampler(0.1, 0.6)
    kw = dict(L=4, K=6, Qcap=48, A_max=5, horizon=150)
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    ref = monte_carlo_bfjs(keys, 1.0, 0.03, sampler, engine="reference", **kw)
    scan = monte_carlo_bfjs(keys, 1.0, 0.03, sampler, engine="scan", **kw)
    pal = monte_carlo_bfjs(keys, 1.0, 0.03, sampler, engine="pallas", **kw)
    assert int(np.asarray(scan.truncated).sum()) == 0
    for res in (scan, pal):
        np.testing.assert_array_equal(np.asarray(res.queue_len),
                                      np.asarray(ref.queue_len))
        np.testing.assert_array_equal(np.asarray(res.departed),
                                      np.asarray(ref.departed))
        np.testing.assert_array_equal(np.asarray(res.dropped),
                                      np.asarray(ref.dropped))


def test_jax_engine_agrees_with_numpy_engine_distributionally():
    """Same workload, both engines: tail queue means within 2x (they use
    different RNG streams; the regime must match)."""
    lam, mu, L = 0.07, 0.01, 5

    def sampler(key, n):
        return jax.random.uniform(key, (n,), minval=0.1, maxval=0.9)

    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    jres = monte_carlo_bfjs(keys, lam, mu, sampler, L=L, K=16, Qcap=512,
                            A_max=6, horizon=12_000)
    jq = float(jres.queue_len[:, -3000:].mean())

    nres = simulate(BFJS(), L=L, lam=lam, dist=Uniform(0.1, 0.9),
                    service=ServiceModel("geometric", 1 / mu),
                    horizon=12_000, seed=0)
    nq = max(nres.mean_queue_tail, 0.3)
    assert jq / nq < 3.0 and nq / max(jq, 0.3) < 3.0, (jq, nq)
