"""Serving substrate: the per-arm queues' selection rule (incl. the
round-robin-aging starvation fix and arrival-armed flush deadlines),
prompt padding, and engine generate."""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import Request, ServingEngine
from repro.serving.batcher import ArmQueues, pad_rows


def _submit(q, target, req):
    """One request into ``q`` as its own chunk, arriving now."""
    q.push(target, [req], q.clock())


def _next(q, force=False):
    """``q.pop`` as (target, requests in arrival order), or None."""
    got = q.pop(force)
    if got is None:
        return None
    target, parts = got
    return target, [r for _, rows in parts for r in rows]


def test_batcher_groups_and_pads():
    b = ArmQueues(max_batch=2)
    r1 = Request(tokens=np.array([1, 2, 3]))
    r2 = Request(tokens=np.array([1, 2, 3, 4, 5]))
    r3 = Request(tokens=np.array([9]))
    _submit(b, 0, r1)
    _submit(b, 0, r2)
    _submit(b, 1, r3)
    assert b.pending() == 3
    target, reqs = _next(b)
    assert target == 0 and reqs == [r1, r2]     # submission order kept
    toks = pad_rows([r.tokens for r in reqs], 4)
    assert toks.shape == (2, 8)  # padded to multiple of 4 over max len 5
    assert list(toks[0][:3]) == [1, 2, 3] and toks[0][3] == 0
    target2, reqs2 = _next(b)
    assert target2 == 1 and reqs2 == [r3]
    assert pad_rows([r3.tokens], 4).shape == (1, 4)
    assert _next(b) is None


def test_batcher_minority_queue_never_starves():
    """Regression: the queues popped the fullest target, so a minority
    target starved indefinitely whenever a majority queue refilled above
    it every round. Round-robin aging bounds the wait."""
    b = ArmQueues(max_batch=2)
    _submit(b, 1, Request(tokens=np.array([7])))    # lone minority request
    served = []
    for _ in range(8):                              # steady majority load
        for _ in range(3):
            _submit(b, 0, Request(tokens=np.array([1, 2])))
        target, _ = _next(b)
        served.append(target)
    assert 1 in served, f"minority target starved: {served}"
    # the wait is bounded at max_starve rounds even under growing backlog
    assert served.index(1) <= b.max_starve
    # and the majority queue still gets the bulk of the batches
    assert served.count(0) > served.count(1)


def test_batcher_age_resets_after_service():
    """A served queue's age resets — it cannot immediately leapfrog a
    fuller queue again on pure age."""
    b = ArmQueues(max_batch=1)
    _submit(b, 0, Request(tokens=np.array([1])))
    _submit(b, 0, Request(tokens=np.array([1])))
    _submit(b, 1, Request(tokens=np.array([2])))
    assert _next(b)[0] == 0             # fullest first
    assert _next(b)[0] == 1             # aged minority wins the tie
    assert _next(b)[0] == 0
    assert _next(b) is None


def test_batcher_flush_deadline_armed_by_arrival_not_epoch():
    """Regression: the flush deadline used to be an epoch timer armed at
    the last flush, so after an empty-then-burst arrival the stale
    deadline had already expired and the first batch flushed
    immediately, undersized. Deadlines must arm per request from its
    OWN arrival time: an idle period leaves nothing armed."""
    now = [0.0]
    b = ArmQueues(max_batch=4, flush_timeout=1.0, clock=lambda: now[0])
    assert _next(b) is None
    now[0] = 50.0                    # long idle gap, then a burst
    _submit(b, 0, Request(tokens=np.array([1])))
    _submit(b, 0, Request(tokens=np.array([2])))
    # stale-deadline bug: a deadline armed at t=0 expired long ago and
    # this pair would flush here, undersized
    assert _next(b) is None
    now[0] = 50.4
    _submit(b, 0, Request(tokens=np.array([3])))
    _submit(b, 0, Request(tokens=np.array([4])))
    target, reqs = _next(b)                  # full batch: always ready
    assert target == 0 and len(reqs) == 4
    # a straggler flushes when ITS OWN age crosses the window...
    _submit(b, 0, Request(tokens=np.array([5])))
    now[0] = 51.3
    assert _next(b) is None                  # 0.9s old < 1.0s window
    now[0] = 51.5
    _, reqs = _next(b)
    assert len(reqs) == 1
    # ...and force (drain) overrides the window
    _submit(b, 0, Request(tokens=np.array([6])))
    _, reqs = _next(b, force=True)
    assert len(reqs) == 1
    assert b.pending() == 0


def test_batcher_flush_timeout_selects_among_ready_queues_only():
    """A queue inside its flush window is waiting, not starving: it is
    skipped (without aging toward starvation service) until ready."""
    now = [0.0]
    b = ArmQueues(max_batch=4, flush_timeout=1.0, clock=lambda: now[0])
    _submit(b, 0, Request(tokens=np.array([1])))   # partial, in-window
    for _ in range(4):
        _submit(b, 1, Request(tokens=np.array([2])))
    target, reqs = _next(b)
    assert target == 1 and len(reqs) == 4        # the full queue wins
    assert _next(b) is None                      # 0 still inside window
    now[0] = 2.0
    assert _next(b)[0] == 0


@pytest.fixture(scope="module")
def tiny_engine():
    cfg = dataclasses.replace(get_config("llama3_2_3b").reduced(),
                              dtype="float32")
    return ServingEngine(cfg, seed=0, max_seq=32)


def test_engine_generates(tiny_engine):
    toks = np.ones((2, 5), np.int32)
    out, _ = tiny_engine.generate(toks, max_new=4)
    assert out.shape == (2, 4)
    assert int(out.max()) < tiny_engine.cfg.vocab_size


def test_engine_deterministic_greedy(tiny_engine):
    toks = np.arange(1, 7, dtype=np.int32)[None]
    a, _ = tiny_engine.generate(toks, max_new=3)
    b, _ = tiny_engine.generate(toks, max_new=3)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
