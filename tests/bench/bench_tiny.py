"""A tiny copy of the benchmark's tree for CPU tests: the real generators,
metrics, peaks, configurations and traffic mixes, with the configurations
cut to 1,200 samples and D̄ = 6.

The limits here are set like the chip's, from readings at this size: the
program's θ reads ~3e-5 and its answers ~2e-7 against the float64
reference, the three-pass control ~8e-4 and ~1.2e-5 (CPU, three seeds
each); θ against the reference's iterate at its own stop reads
3.2e-5..3.8e-5, and 5.2e-4..6.8e-4 with the stop at 10× tol. A limit
between each pair, nearer the program's, separates the two."""
from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

TINY = {"num_samples": 1200, "dbar": 6, "round_budget": 3000, "tol": 1e-5}
LIMITS = {"ddrf_unmatched": 0.0, "ddrf_energy_loss": 0.01,
          "theta_rel_err": 2e-4, "theta_stop_err": 2e-4, "answer_rel_err": 2e-6}


# Every generator's cell, whether or not BENCHMARK.json holds it yet:
# (cell, configuration, traffic mix, chips).
CELLS = [("table2-twitter.fit", "table2-twitter", "fit_closed_loop", 1),
         ("table2-twitter.serve", "table2-twitter", "poisson_single_query",
          1),
         ("fig3-imbalanced-twitter.fit", "fig3-imbalanced-twitter",
          "fit_closed_loop", 1),
         ("twitter-j4-ring.solve", "twitter-j4-ring",
          "spmd_solve_closed_loop", 4)]
END_TO_END = {"fit_s": ["table2-twitter.fit", "fig3-imbalanced-twitter.fit"],
              "answer_p50_ms": ["table2-twitter.serve"],
              "answer_p99_ms": ["table2-twitter.serve"],
              "solve_s": ["twitter-j4-ring.solve"]}


def make_root(tmp, *, traffic=None, limits=None) -> str:
    """A benchmark root under `tmp` whose cells are the generators' cells at
    a tiny size. Returns its path."""
    root = str(tmp)
    os.makedirs(os.path.join(root, "bench", "configs"))
    os.makedirs(os.path.join(root, "bench", "traffic"))
    for sub in ("generators", "metrics", "peaks.json"):
        os.symlink(os.path.join(REPO, "bench", sub),
                   os.path.join(root, "bench", sub))
    configs = sorted({c for _, c, _, _ in CELLS})
    for name in configs:
        with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
            cfg = json.load(f)
        cfg.update(TINY, limits=dict(LIMITS, **(limits or {})))
        with open(os.path.join(root, "bench", "configs", name + ".json"),
                  "w") as f:
            json.dump(cfg, f)
    for mix in sorted({t for _, _, t, _ in CELLS}):
        with open(os.path.join(REPO, "bench", "traffic", mix + ".json")) as f:
            tr = json.load(f)
        tr.update((traffic or {}).get(tr["generator"], {}))
        with open(os.path.join(root, "bench", "traffic", mix + ".json"),
                  "w") as f:
            json.dump(tr, f)
    bench = {
        "configs": [{"name": c, "file": f"bench/configs/{c}.json"}
                    for c in configs],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": k}
                      for n, c, t, k in CELLS],
        "end_to_end": [{"name": m, "unit": "ms" if m.endswith("_ms") else "s",
                        "workloads": w} for m, w in END_TO_END.items()]
        + [{"name": "setup_s", "unit": "s"}],
        "per_layer": []}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_tiny(root, workload, *, seed=7, seconds=1.0, device_count=1):
    """One untraced run of `workload` on the CPU, without the look for a
    chip. Returns the result line as a dict."""
    import time

    import jax

    from bench import harness, registry

    cell = registry.load_cell(workload, root)
    with jax.enable_x64(False):
        out, _ = harness.run_cell(
            cell, seed, seconds, False, t_process=time.perf_counter(),
            trace_dir=os.path.join(root, ".bench_trace"),
            device_count=device_count)
    return out
