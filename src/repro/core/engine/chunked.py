"""The stateful scan runners the chunk driver threads its carry through.

``_STATEFUL`` maps each policy with a stateful scan engine to a
``runner(streams, state, config) -> (PolicyResult, state)``: one chunk of
``lax.scan`` that starts from ``state`` (``None`` = a fresh cluster) and
returns its complete carry (server planes, queue planes, retry/seq
planes, counters, ``up_last``).  :func:`~repro.core.engine.streaming.
stream_policy` looks policies up here for every chunked run: streamed
traces, and the checkpointed sweeps of ``run_policy_streams(chunk=)`` and
``monte_carlo_policy(chunk=)`` (DESIGN.md §9, §12).
"""
from __future__ import annotations

from typing import Callable


def _bfjs_stateful(streams, state, config):
    from .bfjs import run_bfjs_streams
    return run_bfjs_streams(streams, state=state, return_state=True,
                            **config)


def _vqs_stateful(streams, state, config):
    from .vqs import run_vqs_streams
    return run_vqs_streams(streams, state=state, return_state=True,
                           **config)


def _bfjs_mr_stateful(streams, state, config):
    from .bfjs_mr import run_bfjs_mr_streams
    return run_bfjs_mr_streams(streams, state=state, return_state=True,
                               **config)


def _vqs_bf_stateful(streams, state, config):
    from .vqs_bf import run_vqs_bf_streams
    return run_vqs_bf_streams(streams, state=state, return_state=True,
                              **config)


_STATEFUL: dict[str, Callable] = {
    "bfjs": _bfjs_stateful,
    "vqs": _vqs_stateful,
    "bfjs-mr": _bfjs_mr_stateful,
    "vqs-bf": _vqs_bf_stateful,
}
