"""A new cell, configuration, traffic mix, generator and per-layer metric are
found from the files a test drops into a fresh tree, with no other file
edited."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, registry, trace as btrace  # noqa: E402

GENERATOR = '''
import time
import jax.numpy as jnp


def setup(cell, seed, phases):
    return {"n": cell.config["n"], "scale": cell.traffic["scale"]}


def window(state, seconds, phases):
    t0, calls = time.perf_counter(), 0
    while time.perf_counter() - t0 < seconds:
        with phases("echo"):
            state["out"] = float(jnp.sum(jnp.ones(state["n"])) * state["scale"])
        calls += 1
    return {"attempted": calls, "failed": 0, "window_s": seconds,
            "end_to_end": {"echo_s": seconds / calls}, "counts": {"calls": calls}}


def check_outputs(state):
    return [state["out"]]


def judge(state, sample):
    want = state["n"] * state["scale"]
    return [{"echo_err": abs(v - want)} for v in sample]
'''

METRIC = '''
def read(view):
    return view.result["counts"]["calls"] * 1.0
'''


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_bench_new_cell_found_from_files_alone(tmp_path):
    root = str(tmp_path)
    write(os.path.join(root, "bench", "generators", "echo_loop.py"), GENERATOR)
    write(os.path.join(root, "bench", "metrics", "echo_calls.toy.py"), METRIC)
    write(os.path.join(root, "bench", "traffic", "echo_mix.json"),
          json.dumps({"generator": "echo_loop", "scale": 2.0}))
    write(os.path.join(root, "bench", "configs", "toy.json"),
          json.dumps({"n": 5, "limits": {"echo_err": 0.0}}))
    write(os.path.join(root, "BENCHMARK.json"), json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.echo", "config": "toy",
                       "traffic": "echo_mix", "chips": 1}],
        "end_to_end": [{"name": "echo_s", "unit": "s"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "echo_calls.toy", "unit": "calls",
                       "moves": "echo_s", "workloads": ["toy.echo"]},
                      {"name": "other.metric", "unit": "ms",
                       "moves": "echo_s", "workloads": ["elsewhere"]}]}))

    cell = registry.load_cell("toy.echo", root)
    assert cell.config["n"] == 5 and cell.traffic["scale"] == 2.0
    assert [m["name"] for m in cell.per_layer] == ["echo_calls.toy"]
    out, table = harness.run_cell(cell, 3, 0.2, False,
                                  t_process=time.perf_counter(),
                                  trace_dir=os.path.join(root, "t"),
                                  device_count=1)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"echo_s", "setup_s"}
    assert table == {"echo_err": {"value": 0.0, "limit": 0.0}}

    # the per-layer reader is found by the metric's name
    metric = registry.load_metric(root, "echo_calls.toy")
    view = harness.RunView(cell=cell, result={"counts": {"calls": 7}},
                           trace=btrace.Trace(ops={}, spans=[]), devices=[],
                           program_spans=[], peak={}, state={})
    assert metric.read(view) == 7.0


def test_bench_every_listed_piece_exists():
    """Each cell of the repository's BENCHMARK.json resolves, and every
    per-layer metric has its reader."""
    root = registry.ROOT
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = registry.load_cell(w["name"], root)
        assert cell.per_layer and cell.end_to_end
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(registry.load_metric(root, m["name"]).read)
