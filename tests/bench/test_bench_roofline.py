"""Roofline work functions against hand counts at logical shapes."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import roofline  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_bench_eq17_work_two_nodes_hand_count():
    # two nodes joined by one edge: D = (2, 3), N = (5, 7), d = 4
    flops, nbytes = roofline.eq17_work([2, 3], [5, 7], 4, [[1], [0]])
    featurize = 2 * (2 * 4 * 5 + 2 * 4 * 7 + 3 * 4 * 7 + 3 * 4 * 5)
    node0 = (2 * 2 * 2 * 5 + 2 * 2 * 5 + 2 * 2 ** 3
             + 2 * 2 * 2 * 7 + 2 * 2 * 3 * (5 + 7))
    node1 = (2 * 3 * 3 * 7 + 2 * 3 * 7 + 2 * 3 ** 3
             + 2 * 3 * 3 * 5 + 2 * 3 * 2 * (7 + 5))
    assert flops == featurize + node0 + node1
    inputs = 4 * (4 * 5 + 5 + 2 * 5) + 4 * (4 * 7 + 7 + 3 * 5)
    outputs = 4 * (2 * 4 + 2 + 2 * 3) + 4 * (2 * 9 + 3 + 3 * 2)
    assert nbytes == inputs + outputs


def test_bench_eq17_work_ignores_padding():
    # the count depends on each node's own D_j, N_j, not on the maxima
    a, _ = roofline.eq17_work([2, 8], [5, 7], 4, [[1], [0]])
    b, _ = roofline.eq17_work([8, 8], [7, 7], 4, [[1], [0]])
    assert a < b


def test_bench_serve_work_hand_count():
    flops, nbytes = roofline.serve_work([2, 3], 4, 10)
    assert flops == 10 * ((2 * 2 * 4 + 2 * 2) + (2 * 3 * 4 + 2 * 3))
    assert nbytes == 4 * (2 * 6 + 3 * 6) + 4 * 5 * 10


def test_bench_least_seconds_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline.least_seconds(1000.0, 10.0, peak) == (10.0, "flops")
    assert roofline.least_seconds(10.0, 1000.0, peak) == (100.0, "bytes")


def test_bench_peaks_table_and_missing_device():
    peak = roofline.peaks("TPU v5 lite", ROOT)
    assert peak["flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu", ROOT)
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        assert "TPU v5e" in json.load(f)["source"]
