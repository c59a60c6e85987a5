"""The control — the reference put in the program's place, every matrix
product in three bf16 passes, one step below the configuration's float32
at full precision — is judged not correct, at a tiny size on the CPU."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402


@pytest.mark.parametrize("workload", ["table2-twitter.fit",
                                      "twitter-j4-ring.solve"])
def test_bench_control_fit_is_incorrect(tmp_path, workload):
    import jax

    from bench import check, control, deploy, registry

    root = bench_tiny.make_root(tmp_path)
    cell = registry.load_cell(workload, root)
    dep = deploy.build(cell.config)
    readings = []
    with jax.enable_x64(False):
        for seed in (1, 2, 3):
            keys = cell.generator.node_keys(
                {"dep": dep, "key": jax.random.PRNGKey(seed)}, 0)
            readings.append(check.judge_fit(dep, *control.control_fit(
                dep, keys)))
    for numbers in readings:
        ok, table = check.verdict(numbers, cell.config["limits"])
        assert ok is False, table


def test_bench_control_serve_is_incorrect(tmp_path):
    import jax

    from bench import check, control, registry

    root = bench_tiny.make_root(tmp_path)
    cell = registry.load_cell("table2-twitter.serve", root)
    with jax.enable_x64(False):
        for seed in (1, 2, 3):
            ok, table = check.verdict(control.control_serve(cell, seed, 400),
                                      cell.config["limits"])
            assert ok is False, table
