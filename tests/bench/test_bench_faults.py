"""With the timed path broken underneath, a run's `correct` comes out
false: one test per fault a cell can have, at a tiny size on the CPU."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402


def run(tmp_path, workload):
    root = bench_tiny.make_root(
        tmp_path, traffic={"open_loop_serve": {"rate_qps": 300}})
    return bench_tiny.run_tiny(root, workload, seconds=0.5)


# The fit faults, and the number of the check each has to fail.
FIT_FAULTS = {"state_unchanged": "theta_rel_err",
              "answer_altered": "theta_rel_err",
              "stop_round1": "theta_stop_err",
              "stop_10tol": "theta_stop_err",
              "ddrf_random": "ddrf_energy_loss"}


@pytest.mark.parametrize("fault", list(FIT_FAULTS))
def test_bench_fit_fault_is_incorrect(tmp_path, fault):
    from bench import faults

    with faults.planted(fault):
        out = run(tmp_path, "table2-twitter.fit")
    assert out["correct"] is False
    row = out["checks"][FIT_FAULTS[fault]]
    assert row["value"] > row["limit"], out["checks"]


def test_bench_fit_half_batch_is_incorrect(tmp_path):
    from bench import faults

    with faults.planted("half_batch"):
        out = run(tmp_path, "fig3-imbalanced-twitter.fit")
    assert out["correct"] is False


@pytest.mark.parametrize("fault", ["answer_altered", "half_the_nodes"])
def test_bench_serve_fault_is_incorrect(tmp_path, fault):
    from bench import faults

    with faults.planted({"answer_altered": "serve_altered",
                         "half_the_nodes": "serve_half_nodes"}[fault]):
        out = run(tmp_path, "table2-twitter.serve")
    assert out["correct"] is False
    assert out["checks"]["answer_rel_err"]["value"] > \
        out["checks"]["answer_rel_err"]["limit"]


SPMD = """
import json, sys
sys.path.insert(0, {here!r})
import bench_tiny
from bench import faults
with faults.planted({fault!r}):
    root = bench_tiny.make_root({root!r})
    print(json.dumps(bench_tiny.run_tiny(root, "twitter-j4-ring.solve",
                                         seconds=0.5, device_count=4)))
"""


def run_spmd(tmp_path, fault):
    """A tiny run of the 4-node SPMD cell with `fault` planted, on four
    virtual CPU devices in a process of its own."""
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    code = SPMD.format(here=os.path.dirname(os.path.abspath(__file__)),
                       root=str(tmp_path), fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_bench_spmd_without_exchange_is_incorrect(tmp_path):
    out = run_spmd(tmp_path, "no_exchange")
    assert out["correct"] is False
    assert out["checks"]["theta_rel_err"]["value"] > \
        out["checks"]["theta_rel_err"]["limit"]


def test_bench_spmd_early_stop_is_incorrect(tmp_path):
    out = run_spmd(tmp_path, "stop_10tol")
    assert out["correct"] is False
    assert out["checks"]["theta_stop_err"]["value"] > \
        out["checks"]["theta_stop_err"]["limit"]
