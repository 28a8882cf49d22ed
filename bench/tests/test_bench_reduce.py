"""The trace reduction on a hand-made trace (every number worked out by
hand) and on a small trace recorded on the chip."""
from pathlib import Path

import pytest

from bench_fixtures import ROOT  # noqa: F401

from bench import reduce

MS = 1e6  # ns


def table():
    """Window 0-100 ms. Device 0: ops at 0-10, 5-20 (overlap), 30-40 and
    a collective at 50-70 with a fused op beside it at 60-65; device 1:
    one op at 10-30. A round program spans 0-40 on device 0."""
    ops0 = [("fusion.1", 0, 10 * MS), ("_vecavg_kernel", 5 * MS, 15 * MS),
            ("fusion.2", 30 * MS, 10 * MS), ("all-reduce.3", 50 * MS, 20 * MS),
            ("fusion.4", 60 * MS, 5 * MS), ("fusion.5", 95 * MS, 20 * MS)]
    return {
        "devices": {
            "/device:TPU:0": {"XLA Ops": ops0,
                              "XLA Modules": [("jit_fused(1)", 0, 40 * MS)]},
            "/device:TPU:1": {"XLA Ops": [("fusion.9", 10 * MS, 20 * MS)],
                              "XLA Modules": []},
        },
        "host": [("bench:window", 0, 100 * MS), ("bench:tick", 20 * MS, 8 * MS),
                 ("bench:wait", 70 * MS, 25 * MS)],
    }


def test_busy_is_the_union_clipped_to_the_window():
    r = reduce.Reduced(table())
    assert r.window_s == pytest.approx(0.1)
    # device 0: 0-20, 30-40, 50-70, 95-100 = 55 ms; device 1: 20 ms
    assert r.busy_s() == pytest.approx((0.055 + 0.020) / 2)
    assert r.idle_share() == pytest.approx(1 - 0.0375 / 0.1)


def test_sums_by_name():
    r = reduce.Reduced(table())
    assert r.op_count([r"vecavg"]) == 1
    assert r.op_time_s([r"vecavg"]) == pytest.approx(0.015 / 2)
    assert r.module_count([r"fused"]) == 1
    assert r.module_time_s([r"fused"]) == pytest.approx(0.040 / 2)


def test_collectives_and_their_exposed_part():
    tot, exposed = reduce.Reduced(table()).collective_s()
    assert tot == pytest.approx(0.020 / 2)
    assert exposed == pytest.approx(0.015 / 2)  # 5 ms had fusion.4 beside it


def test_breakdown():
    r = reduce.Reduced(table())
    top = dict(r.top_ops(3))
    assert top["all-reduce"] == pytest.approx(0.020)
    gaps = r.idle_gaps(3)
    # device 0 idle: 20-30 (tick open), 40-50 (nothing), 70-95 (wait)
    assert gaps[0] == ["bench:wait", pytest.approx(0.025)]
    assert sorted(g[0] for g in gaps[1:]) == ["bench:tick", "host"]


def test_interval_helpers():
    assert reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert reduce.clip([("a", 5, 10)], 0, 8) == [("a", 5, 3)]


def test_save_and_load_round_trip(tmp_path):
    p = tmp_path / "t.json.gz"
    reduce.save(table(), str(p))
    back = reduce.load(str(p))
    assert reduce.Reduced(back).busy_s() == reduce.Reduced(table()).busy_s()


def test_op_names_drop_the_instruction_text():
    name = ("%fusion.7 = f32[5,32]{1,0} fusion(f32[5] %vecavg_pallas.3), "
            "kind=kLoop")
    assert reduce.op_name(name) == "fusion.7"
    assert reduce.op_name("jit__decode(1355)") == "jit__decode(1355)"


def _bitmap_busy(events, lo, hi, step=10.0):
    """Busy time by brute force: 10 ns cells covered by any event."""
    import numpy as np

    cells = np.zeros(int((hi - lo) / step) + 1, bool)
    for _, s, d in events:
        a = max(int((s - lo) // step), 0)
        b = min(int(np.ceil((s + d - lo) / step)), len(cells))
        cells[a:b] = True
    return cells.sum() * step * 1e-9


@pytest.mark.parametrize("name,span_ms", [("decode_window_v5e", 20.0),
                                          ("round_window_v5e", 5.0)])
def test_recorded_chip_trace(name, span_ms):
    """Traces recorded on a TPU v5e (the first milliseconds of a window):
    the union of busy intervals agrees with a brute-force count, the
    named programs and kernels are found, and nothing exceeds the
    window."""
    t = reduce.load(str(Path(__file__).resolve().parents[1] / "testdata"
                        / f"{name}.json.gz"))
    lo, _ = reduce.window_bounds(t)
    hi = lo + span_ms * MS
    r = reduce.Reduced(t, lo, hi)
    ops = r.ops["/device:TPU:0"]
    # each event's end rounds up by at most one 10 ns cell
    assert r.busy_s() == pytest.approx(_bitmap_busy(ops, lo, hi),
                                       abs=len(ops) * 10e-9)
    assert 0.5 < r.busy_s() / r.window_s <= 1.0
    assert r.top_ops(1)[0][1] <= r.window_s
    if name.startswith("decode"):
        assert r.module_count([r"_decode\b|_decode\("]) == 1
        k = r.op_time_s([r"paged_decode_attention"])
        assert 0.5 * r.busy_s() < k < r.busy_s()
    else:
        assert not r.op_count([r"paged_decode_attention"])
