"""The program's own spans and counts, read from the profiler's timeline."""
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, program_trace as pt, registry  # noqa: E402
from bench import trace as bt  # noqa: E402

DEV = "/device:TPU:0"
READERS = ("ddrf_device_ms.fit", "ddrf_ops.fit", "pack_stage_ms.fit",
           "pack_gram_ms.fit", "ddrf_h2d_mb.fit", "pack_h2d_mb.fit",
           "compiles.fit")


def two_fits():
    """Two fits' DDRF and pack phases. The second fit's last selection
    closes at 520 while its ops run to 580, before the phase's sync."""
    ops = {DEV: [("warm", 10, 40),
                 ("cos.1", 100, 110), ("fusion.1", 130, 150),
                 ("sort", 160, 190),              # after both selections
                 ("gram", 250, 300),              # pack: not DDRF's
                 ("cos.1", 500, 510), ("fusion.1", 515, 560),
                 ("sort", 562, 580),
                 ("gram", 650, 700)]}
    spans = [("bench.window", 0, 1000, {}),
             ("bench.ddrf", 90, 200, {}), ("bench.pack", 200, 320, {}),
             ("bench.ddrf", 480, 600, {}), ("bench.pack", 600, 720, {})]
    program = [("repro.ddrf.select", 95, 120, {"d0": 20}),
               ("repro.count.ddrf.h2d_bytes", 96, 96, {"n": 7_000_000}),
               ("repro.ddrf.select", 120, 140, {"d0": 20}),
               ("repro.count.ddrf.h2d_bytes", 121, 121, {"n": 8_000_000}),
               ("repro.pack_problem", 210, 310, {"nodes": 10}),
               ("repro.count.pack.h2d_bytes", 220, 220, {"n": 80_000_000}),
               ("repro.ddrf.select", 490, 520, {"d0": 20}),
               ("repro.count.ddrf.h2d_bytes", 491, 491, {"n": 15_000_000}),
               ("repro.count.pack.h2d_bytes", 1200, 1200, {"n": 1})]
    return bt.Trace(ops=ops, spans=spans), program


def test_bench_program_prefixes_are_the_programs():
    from repro.obs import spans

    assert pt.PREFIX == spans.TIMELINE_PREFIX
    assert pt.COUNT_PREFIX == spans.COUNT_PREFIX


def test_bench_ddrf_device_work_takes_in_the_ops_run_after_the_span():
    tr, program = two_fits()
    windows = pt.dispatched_by(tr, program, "ddrf.select", "ddrf")
    assert windows == [(95, 200), (490, 600)]
    # 3 ops a fit; the spans alone miss each fit's last op
    assert pt.ops_starting_in(tr, DEV, windows) == 6
    assert pt.ops_starting_in(tr, DEV, pt.intervals(
        program, "ddrf.select")) == 4
    assert pt.busy_in(tr, DEV, windows) == (10 + 20 + 30) + (10 + 45 + 18)
    assert pt.dispatched_by(tr, program, "ddrf.select", "pack") == []
    assert pt.dispatched_by(tr, program, "pack_problem", "pack") == [
        (210, 320)]


def test_bench_program_counts_inside_the_window():
    tr, program = two_fits()
    assert pt.total(tr, program, "ddrf.h2d_bytes") == 30_000_000
    assert pt.total(tr, program, "pack.h2d_bytes") == 80_000_000  # not 1200
    assert pt.total(tr, program, "jax.compiles") == 0
    assert pt.total(tr, [], "jax.compiles") is None


def view_of(tr, program_spans, fits, root="/nonexistent"):
    return harness.RunView(
        cell=types.SimpleNamespace(root=str(root)),
        result={"counts": {"fits": fits}}, trace=tr, devices=[DEV],
        program_spans=program_spans, peak={}, state={})


def read(name, view):
    return registry.load_metric(registry.ROOT, name).read(view)


def test_bench_program_metric_readers(monkeypatch):
    """The seven readers, per fit; None from a program that puts nothing
    on the timeline (an older checkout) and from a window with no fit."""
    from repro.obs.spans import Span

    def sp(name, t0, t1):
        return Span(name=name, t_start=t0, t_end=t1, depth=1,
                    parent="pack_problem", thread="main", attrs={})

    spans = [sp("pack.stage", 0.0, 0.030), sp("pack.gram", 0.030, 0.050),
             sp("pack.stage", 1.0, 1.050), sp("pack.gram", 1.05, 1.06)]
    tr, program = two_fits()
    monkeypatch.setattr(pt, "events", lambda view: program)
    view = view_of(tr, spans, 2)
    assert read("ddrf_device_ms.fit", view) == pytest.approx(133 / 2 / 1e6)
    assert read("ddrf_ops.fit", view) == 3.0
    assert read("pack_stage_ms.fit", view) == pytest.approx(40.0)
    assert read("pack_gram_ms.fit", view) == pytest.approx(15.0)
    assert read("ddrf_h2d_mb.fit", view) == pytest.approx(15.0)
    assert read("pack_h2d_mb.fit", view) == pytest.approx(40.0)
    assert read("compiles.fit", view) == 0.0
    for name in READERS:
        assert read(name, view_of(tr, spans, 0)) is None, name
    monkeypatch.setattr(pt, "events", lambda view: [])
    for name in READERS:
        assert read(name, view_of(tr, [], 2)) is None, name


def test_bench_program_events_from_a_profiler_trace(tmp_path):
    """On a CPU profiler trace laid out as a traced run leaves it, the
    program's spans and count markers are read back with their stats,
    and the readers take them; a trace that is not the view's is not."""
    import jax

    from repro.core import select_features
    from repro.obs.spans import recording

    rng = np.random.default_rng(0)
    x = rng.normal(size=(5, 200)).astype(np.float32)
    y = x[0].copy()
    key = jax.random.PRNGKey(0)
    select_features(key, 5, 8, 1.0, x, y, candidate_ratio=5)   # compile
    with recording() as rec, jax.profiler.trace(
            str(tmp_path / pt.TRACE_DIR),
            profiler_options=bt.profiler_options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.ddrf"):
                jax.block_until_ready(
                    select_features(key, 5, 8, 1.0, x, y, candidate_ratio=5))
    path, = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
             for f in fs if f.endswith(".xplane.pb")]
    st = os.stat(path)
    bench, _ = pt._load(path, st.st_mtime_ns, st.st_size)
    assert [n for n, *_ in bench] == ["bench.window", "bench.ddrf"]

    view = view_of(bt.Trace(ops={}, spans=bench), list(rec.spans), 1,
                   root=tmp_path)
    program = pt.events(view)
    sel = [e for e in program if e[0] == "repro.ddrf.select"]
    assert len(sel) == 1 and sel[0][3] == {"d0": 40, "D": 8, "N": 200}
    marks = [e for e in program if e[0].startswith(pt.COUNT_PREFIX)]
    assert [(n, st["n"]) for n, _, _, st in marks] == [
        ("repro.count.ddrf.h2d_bytes", x.nbytes + y.nbytes)]
    assert sel[0][1] <= marks[0][1] < sel[0][2]
    assert read("ddrf_h2d_mb.fit", view) == pytest.approx(
        rec.counts["ddrf.h2d_bytes"] / 1e6)
    assert read("compiles.fit", view) == rec.counts["jax.compiles"] == 0
    assert read("ddrf_ops.fit", view) == 0.0       # no device plane on CPU

    other = view_of(bt.Trace(ops={}, spans=bench[:1]), [], 1, root=tmp_path)
    assert pt.events(other) == []
