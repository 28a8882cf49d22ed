"""The wall-clock queue and the end-to-end arithmetic of a serve window:
requests leave in due order, and rates and tails are taken over every
request and the whole window, so one stall moves them."""
import numpy as np
import pytest

from bench_fixtures import ROOT, fixture_cell  # noqa: F401

from bench import stats
from bench.kinds import paged_serve


class FakeReq:
    def __init__(self, rid, plen, walls, admit_tick=0):
        self.rid, self.plen, self.tok_walls = rid, plen, walls
        self.admit_tick = admit_tick
        self.out = list(range(len(walls)))


def test_queue_releases_in_due_order(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(paged_serve.time, "time", lambda: now[0])
    reqs = [FakeReq(rid, 8, []) for rid in "cadb"]
    q = paged_serve.WallClockQueue(reqs, [103.0, 101.0, 104.0, 102.0])
    assert q.peek_arrived(0) is None and len(q) == 4
    out = []
    for t in (101.5, 102.5, 103.5, 104.5):
        now[0] = t
        while (r := q.pop_arrived(999)) is not None:
            out.append(r.rid)
    assert out == ["a", "b", "c", "d"] and len(q) == 0
    assert q.popped == {"a": 101.5, "b": 102.5, "c": 103.5, "d": 104.5}
    q2 = paged_serve.WallClockQueue([FakeReq("x", 8, []),
                                     FakeReq("y", 8, [])], [101.0, 150.0])
    q2.horizon = 120.0
    now[0] = 200.0
    assert q2.pop_arrived(0).rid == "x" and q2.pop_arrived(0) is None


def _window(stalls=(), stall=0.0, n_req=20, steps=50):
    """n_req requests decoding every 10 ms from t=0; each stall delays
    every token after it by ``stall``."""
    reqs = []
    for i in range(n_req):
        walls = []
        for j in range(steps):
            t = 0.001 * i + 0.01 * j
            walls.append(t + stall * sum(t >= s for s in stalls))
        reqs.append(FakeReq(i, 64, walls))
    counters = dict(t0=0.0, t_end=0.5, decode_dispatches=steps,
                    prefill_dispatches=n_req, popped={},
                    backlog=False, due_wall={r.rid: 0.001 * r.rid
                                             for r in reqs},
                    due_in=[r.rid for r in reqs], compiles_in_window=0)
    cell = fixture_cell("decoder-tiny.chat-tiny")
    return paged_serve.measure(reqs, counters, cell)


def test_rate_counts_every_token_in_the_whole_window():
    e2e, c = _window()
    inside = sum(1 for i in range(20) for j in range(50)
                 if 0 <= 0.001 * i + 0.01 * j < 0.5)
    assert c["tokens"] == inside
    assert e2e["serve_tok_s"] == pytest.approx(inside / 0.5)


def test_a_stall_moves_rate_and_tails():
    base, _ = _window()
    # three 40 ms stalls: one gap in twelve of every request is 50 ms
    hit, _ = _window(stalls=(0.1005, 0.2005, 0.3005), stall=0.04)
    assert hit["serve_tok_s"] < 0.8 * base["serve_tok_s"]
    assert base["itl_p95_ms"] == pytest.approx(10.0, rel=1e-6)
    assert hit["itl_p95_ms"] == pytest.approx(50.0, rel=1e-6)


def test_ttft_counts_from_due_time_and_waits_for_late_tokens():
    e2e, _ = _window()
    # first tokens land exactly at their due times
    assert e2e["ttft_p90_ms"] == pytest.approx(0.0, abs=1e-6)
    reqs = [FakeReq(0, 64, [0.3, 0.31]), FakeReq(1, 64, [0.9])]
    counters = dict(t0=0.0, t_end=0.5, decode_dispatches=1,
                    prefill_dispatches=2, popped={0: 0.2}, backlog=False,
                    due_wall={0: 0.1, 1: 0.4}, due_in=[0, 1],
                    compiles_in_window=0)
    e2e, _ = paged_serve.measure(reqs, counters,
                                 fixture_cell("decoder-tiny.chat-tiny"))
    # request 1's first token came after the close: its wait still counts
    assert e2e["ttft_p90_ms"] == pytest.approx(
        1e3 * stats.percentile([0.2, 0.5], 90))


def test_percentile_and_spread():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert np.isnan(stats.percentile([], 90))
    assert stats.spread([10, 10, 10, 10]) == 0.0
    assert stats.spread([9, 10, 11, 12, 8]) > 0
