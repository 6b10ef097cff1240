"""A run with the timed path broken underneath comes out not correct, once
for each fault a cell can have on one chip: a step that returns its state
unchanged, half of the batch left out, an answer altered where it is
produced.  (No cell spans chips, so none can lose an exchange between
them.)"""
import jax
import jax.numpy as jnp
import pytest

import repro.core.engine as engine_api
from repro.core.engine import chunked

SWEEPS = ["synthetic-1000.vqsbf-kernel", "synthetic-1000.vqsbf-scan",
          "synthetic-1000.bfjs-scan"]
REPLAY = "synthetic-1000.replay-vqsbf"


def frozen(res):
    """The trajectory of a step that never changes its state."""
    return res._replace(queue_len=jnp.zeros_like(res.queue_len),
                        occupancy=jnp.zeros_like(res.occupancy),
                        departed=jnp.zeros_like(res.departed))


def altered(res):
    """One slot's queue length off by one, in every member."""
    q = res.queue_len
    return res._replace(queue_len=q.at[..., q.shape[-1] // 2].add(1))


def sweep_fault(kind):
    real = engine_api.monte_carlo_policy

    def broken(workload, keys, **kw):
        if kind == "half_batch":
            half = keys.shape[0] // 2
            res = real(workload, keys[:half], **kw)
            return jax.tree.map(lambda x: jnp.concatenate([x, x]), res)
        res = real(workload, keys, **kw)
        return frozen(res) if kind == "state_unchanged" else altered(res)
    return broken


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
@pytest.mark.parametrize("name", SWEEPS)
def test_broken_sweep_is_not_correct(tiny_run, monkeypatch, name, kind):
    monkeypatch.setattr(engine_api, "monte_carlo_policy", sweep_fault(kind))
    line = tiny_run(name)["line"]
    assert not line["correct"]
    assert line["checks"]["mismatched_slots"]["value"] > 0


def test_replay_step_returning_its_state_unchanged(tiny_run, monkeypatch):
    real = chunked._STATEFUL["vqs-bf"]

    def stuck(streams, state, config):
        res, new = real(streams, state, config)
        return res, new if state is None else state
    monkeypatch.setitem(chunked._STATEFUL, "vqs-bf", stuck)
    line = tiny_run(REPLAY)["line"]
    assert not line["correct"]
    assert line["checks"]["mismatched_slots"]["value"] > 0


def test_replay_with_half_of_each_chunk_left_out(tiny_run, monkeypatch):
    real = engine_api.stream_chunks_from_trace

    def halved(*a, **kw):
        for chunk in real(*a, **kw):
            yield chunk._replace(n=chunk.n // 2)
    monkeypatch.setattr(engine_api, "stream_chunks_from_trace", halved)
    line = tiny_run(REPLAY)["line"]
    assert not line["correct"]
    assert line["checks"]["mismatched_slots"]["value"] > 0


def test_replay_answer_altered(tiny_run, monkeypatch):
    real = engine_api.stream_policy
    monkeypatch.setattr(engine_api, "stream_policy",
                        lambda *a, **kw: altered(real(*a, **kw)))
    line = tiny_run(REPLAY)["line"]
    assert not line["correct"]
    assert line["checks"]["mismatched_slots"]["value"] > 0
