"""The padding-share readers: what share of the Eq. 17 build and of each
Eq. 19 round's G, d, S, P elements the packed layout adds, from the
shapes the program's `pack.stage` and `solve.batched` spans state."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402  (puts the repository on sys.path)

from bench import deploy, harness, registry, roofline  # noqa: E402
from bench import trace as bt  # noqa: E402
from repro.data.synthetic import imbalanced_sizes  # noqa: E402
from repro.obs.spans import Span  # noqa: E402

READERS = ("pack_pad_share.fit", "solve_pad_share.fit")
GRAPH = {"kind": "circulant", "offsets": [1, 2]}
DIM = 7


def read(name, view):
    return registry.load_metric(registry.ROOT, name).read(view)


def deployment(widths, sizes):
    """A J = len(widths) deployment on circulant(J, (1, 2)) whose node j
    holds sizes[j] samples and draws widths[j] features."""
    x = [np.zeros((DIM, n), np.float32) for n in sizes]
    y = [np.zeros(n, np.float32) for n in sizes]
    return deploy.Deployment(
        x_train=x, y_train=y, x_test=x, y_test=y, widths=list(widths),
        adjacency=registry.adjacency_of(GRAPH, len(widths)), config={})


def spans_of(fits, blocks, d_max):
    """Each fit's pack.stage spans, one per padded block of (nodes, D, N),
    then its solve.batched span."""
    out = []
    for i in range(fits):
        for nodes, dd, nn in blocks:
            out.append(Span("pack.stage", i, i + 0.5, 1, "pack_problem",
                            "main", dict(nodes=nodes, slots=4, f_max=dd,
                                         d_max=dd, n_max=nn)))
        out.append(Span("solve.batched", i + 0.5, i + 1, 0, None, "main",
                        dict(nodes=sum(b[0] for b in blocks), slots=4,
                             d_max=d_max)))
    return out


def view_of(dep, spans, fits):
    return harness.RunView(
        cell=None, result={"counts": {"fits": fits}},
        trace=bt.Trace(ops={}, spans=[]), devices=[], program_spans=spans,
        peak={}, state={"dep": dep})


def imbalanced(n=1200, dbar=6):
    """N_j = (2j−1)·N/100 and D_j = √N_j·J·D̄/Σ√N_i, as fig3 deals them."""
    sizes = imbalanced_sizes(n, 10)
    return registry.feature_widths("sqrt_n", dbar, sizes), sizes


def test_bench_pad_share_zero_for_equal_shards():
    dep = deployment([6] * 10, [60] * 10)
    for fits in (1, 3):
        view = view_of(dep, spans_of(fits, [(10, 6, 60)], 6), fits)
        assert [read(m, view) for m in READERS] == [0.0, 0.0]


def test_bench_pad_share_reads_the_shape_arithmetic_when_imbalanced():
    widths, sizes = imbalanced()
    assert sizes == [12 * (2 * j - 1) for j in range(1, 11)]
    dep = deployment(widths, sizes)
    d_max, n_max = max(widths), max(sizes)
    nbrs = [dep.neighbors(j) for j in range(10)]
    logical, _ = roofline.eq17_work(widths, sizes, DIM, nbrs)
    padded, _ = roofline.eq17_work([d_max] * 10, [n_max] * 10, DIM, nbrs)
    elems = sum(2 * widths[j] ** 2 + widths[j]
                + sum(widths[j] * widths[p] for p in nbrs[j])
                for j in range(10))
    want = [100 * (1 - logical / padded),
            100 * (1 - elems / (10 * (6 * d_max ** 2 + d_max)))]
    for fits in (1, 2):
        view = view_of(dep, spans_of(fits, [(10, d_max, n_max)], d_max),
                       fits)
        got = [read(m, view) for m in READERS]
        assert got == pytest.approx(want, rel=1e-12)
    assert 0 < want[1] < want[0] < 100

    # two padded blocks a fit (the five small nodes, the five large):
    # the reader sums the work over a fit's pack.stage spans
    blocks = [(5, max(widths[:5]), max(sizes[:5])), (5, d_max, n_max)]
    block_work = sum(roofline.eq17_work(
        [dd] * 5, [nn] * 5, DIM, [[(i + 1 + s) % 5 for s in range(4)]
                                  for i in range(5)])[0]
        for _, dd, nn in blocks)
    view = view_of(dep, spans_of(2, blocks, d_max), 2)
    assert read("pack_pad_share.fit", view) == pytest.approx(
        100 * (1 - logical / block_work), rel=1e-12)


def test_bench_pad_share_none_without_the_shapes():
    """A program whose spans state no shape (an older checkout), a window
    with no such span, or no fit, reads None."""
    widths, sizes = imbalanced()
    dep = deployment(widths, sizes)
    spans = spans_of(1, [(10, max(widths), max(sizes))], max(widths))
    bare = [Span(s.name, s.t_start, s.t_end, s.depth, s.parent, s.thread, {})
            for s in spans]
    partial = [Span(s.name, s.t_start, s.t_end, s.depth, s.parent, s.thread,
                    {"nodes": 10}) for s in spans]
    for name in READERS:
        assert read(name, view_of(dep, bare, 1)) is None, name
        assert read(name, view_of(dep, partial, 1)) is None, name
        assert read(name, view_of(dep, [], 1)) is None, name
    assert read("pack_pad_share.fit", view_of(dep, spans, 0)) is None


@pytest.mark.parametrize("workload,padded", [
    ("table2-twitter.fit", False), ("fig3-imbalanced-twitter.fit", True)])
def test_bench_pad_share_of_a_tiny_recorded_window(tmp_path, workload,
                                                   padded):
    """The fit loop's window at a tiny size, under the program's span
    recorder: table2's equal shards read 0, fig3's the arithmetic at its
    own D_max and N_max."""
    import jax

    from repro.obs.spans import recording

    cell = registry.load_cell(workload, bench_tiny.make_root(tmp_path))
    gen = cell.generator
    with jax.enable_x64(False):
        state = gen.setup(cell, 11, harness.Phases(False))
        with recording() as rec:
            result = gen.window(state, 0.2, harness.Phases(False))
    dep = state["dep"]
    view = view_of(dep, list(rec.spans), result["counts"]["fits"])
    got = [read(m, view) for m in READERS]
    if not padded:
        assert got == [0.0, 0.0]
        return
    widths, sizes = dep.widths, [x.shape[1] for x in dep.x_train]
    d_max, n_max = max(widths), max(sizes)
    assert min(widths) < d_max and min(sizes) < n_max
    nbrs = [dep.neighbors(j) for j in range(10)]
    logical, _ = roofline.eq17_work(widths, sizes, dep.dim, nbrs)
    padded_work, _ = roofline.eq17_work([d_max] * 10, [n_max] * 10,
                                        dep.dim, nbrs)
    assert got[0] == pytest.approx(100 * (1 - logical / padded_work),
                                   rel=1e-12)
    assert 0 < got[1] < got[0]
