"""Each traffic kind, run at a tiny size on the CPU (kernels interpret),
through the harness's whole run but the look for a chip."""
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402


@pytest.mark.parametrize("workload,metrics", [
    ("table2-twitter.fit", {"fit_s", "setup_s"}),
    ("fig3-imbalanced-twitter.fit", {"fit_s", "setup_s"}),
    ("table2-twitter.serve", {"answer_p50_ms", "answer_p99_ms", "setup_s"}),
])
def test_bench_generator_runs_correct(tmp_path, workload, metrics):
    root = bench_tiny.make_root(
        tmp_path, traffic={"open_loop_serve": {"rate_qps": 300}})
    out = bench_tiny.run_tiny(root, workload, seconds=0.6)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == metrics
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert all(row["value"] <= row["limit"]
               for row in out["checks"].values())


SPMD = """
import json, sys
sys.path.insert(0, {here!r})
import bench_tiny
root = bench_tiny.make_root({root!r})
print(json.dumps(bench_tiny.run_tiny(root, "twitter-j4-ring.solve",
                                     seconds=0.6, device_count=4)))
"""


def spmd_env():
    return {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
            "HOME": os.environ.get("HOME", "/tmp"),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def test_bench_generator_spmd_solve_on_four_devices(tmp_path):
    import json

    code = SPMD.format(here=os.path.dirname(os.path.abspath(__file__)),
                       root=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", code], env=spmd_env(),
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"solve_s", "setup_s"}
    assert out["device"]["count"] == 4


def test_bench_run_exits_without_tpu():
    repo = bench_tiny.REPO
    env = dict(spmd_env(), XLA_FLAGS="")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench", "run.py"),
         "--workload", "table2-twitter.fit", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
