"""The reduction from a trace to the per-layer numbers."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import trace as bt  # noqa: E402

DEV = "/device:TPU:0"


def small_trace():
    ops = {DEV: [("fusion.1", 100, 200), ("fusion.2", 150, 300),
                 ("collective-permute.3", 400, 450), ("all-reduce.1", 500, 520),
                 ("dot.4", 600, 700), ("late", 990, 1100)],
           "/device:TPU:1": [("all-gather.2", 0, 500)]}
    spans = [("bench.window", 0, 1000, {}), ("bench.pack", 90, 320, {}),
             ("bench.solve", 390, 720, {}), ("bench.solve", 980, 1000, {})]
    return bt.Trace(ops=ops, spans=spans)


def test_bench_busy_union_and_idle_share():
    tr = small_trace()
    # [100, 300) ∪ [400, 450) ∪ [500, 520) ∪ [600, 700) ∪ [990, 1000)
    assert bt.busy_ns(tr, DEV, 0, 1000) == 200 + 50 + 20 + 100 + 10
    assert bt.idle_share(tr, [DEV]) == pytest.approx(100 * (1 - 380 / 1000))
    assert bt.idle_share(tr) == pytest.approx(
        100 * ((1 - 0.38) + (1 - 0.5)) / 2)


def test_bench_device_time_inside_host_spans():
    tr = small_trace()
    assert bt.busy_in_spans(tr, DEV, "bench.pack") == 200
    assert bt.busy_in_spans(tr, DEV, "bench.solve") == 50 + 20 + 100 + 10
    assert bt.mean_span_ms(tr, "bench.solve") == pytest.approx(
        (330 + 20) / 2 / 1e6)
    assert bt.mean_span_ms(tr, "bench.ddrf") is None


def test_bench_which_ops_count_as_collectives():
    for name in ("collective-permute.3", "all-reduce.1", "all-gather.2",
                 "reduce-scatter", "all-to-all.7", "collective-permute-done"):
        assert bt.is_collective(name), name
    for name in ("fusion.1", "dot.4", "copy-start", "rff_gram"):
        assert not bt.is_collective(name), name
    tr = small_trace()
    assert bt.collective_ns(tr, DEV) == 70
    assert bt.collective_ns(tr, "/device:TPU:1") == 500


def test_bench_breakdown_top_ops_and_idle_gaps():
    tr = small_trace()
    top = dict(bt.top_ops(tr, n=3))
    assert top["all-gather.2"] == pytest.approx(500 / 2 / 1e9)
    assert top["fusion.2"] == pytest.approx(150 / 2 / 1e9)
    # idle [700, 990) lies between phases; [520, 600) inside a solve
    gaps = bt.idle_gaps(tr, n=5)
    assert [g[0] for g in gaps] == ["bench.window"] * 3 + ["bench.solve"] * 2
    assert [round(g[1] * 1e9) for g in gaps] == [290, 100, 100, 80, 50]


def test_bench_merge_and_overlap():
    merged = bt.merge([(5, 9), (1, 3), (2, 4), (9, 12), (20, 20)])
    assert merged == [(1, 4), (5, 12)]
    assert bt.overlap(merged, 3, 6) == 2


def recorded():
    import json

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "trace_fit_excerpt.json")) as f:
        rec = json.load(f)
    ops = {d: [tuple(o) for o in v] for d, v in rec["ops"].items()}
    spans = [(n, s, e, {}) for n, s, e in rec["spans"]]
    return bt.Trace(ops=ops, spans=spans)


def brute_busy(ops, lo, hi):
    """Busy ns by sweeping the event edges one by one."""
    edges = sorted([(max(s, lo), 1) for _, s, e in ops if e > lo and s < hi]
                   + [(min(e, hi), -1) for _, s, e in ops
                      if e > lo and s < hi])
    busy, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_bench_recorded_trace_reduction():
    """A recorded excerpt of a traced fit window on the chip: the busy
    union matches a brute-force sweep, the idle share and the device time
    inside the DDRF span follow from it, and no op of a one-chip fit is a
    collective."""
    tr = recorded()
    lo, hi = tr.window()
    ops = tr.ops[DEV]
    assert len(ops) > 100
    busy = bt.busy_ns(tr, DEV, lo, hi)
    assert busy == brute_busy(ops, lo, hi)
    assert 0 < busy < hi - lo
    assert bt.idle_share(tr, [DEV]) == pytest.approx(
        100 * (1 - busy / (hi - lo)))
    (d0, d1), = tr.span_intervals("bench.ddrf")
    assert bt.busy_in_spans(tr, DEV, "bench.ddrf") == brute_busy(ops, d0, d1)
    assert not any(bt.is_collective(n) for n, _, _ in ops)
    assert bt.collective_ns(tr, DEV) == 0
