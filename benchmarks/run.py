"""Benchmark runner — one benchmark per paper table/figure plus the kernel
microbench, the §II-C communication-cost model, the §III convergence check
and the roofline aggregation. Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME]

Observability (repro.obs):

  * every suite runs inside a host-side span (``bench.<name>``) recorded
    into one `repro.obs.metrics.Registry`, exported as JSONL to
    ``--obs-jsonl`` (default ``BENCH_run.jsonl``) — render it with
    ``python -m repro.obs BENCH_run.jsonl``;
  * every ``BENCH_*.json`` artifact in the repo root is stamped with a
    run-provenance block (git sha, jax version, device kind, platform,
    interpret flag) after the suites finish;
  * ``--profile-dir DIR`` wraps the whole run in a ``jax.profiler``
    trace for TensorBoard/Perfetto inspection.

Compiled programs persist across runs in JAX's compilation cache
(`repro.compile_cache`: ``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/``).
"""
import argparse
import glob
import os
import sys
import traceback

import jax

jax.config.update("jax_enable_x64", True)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced grids (CI budget)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--obs-jsonl", default=os.path.join(REPO_ROOT,
                                                        "BENCH_run.jsonl"),
                    help="telemetry JSONL output ('' disables)")
    ap.add_argument("--profile-dir", default=None,
                    help="write a jax.profiler trace to this directory")
    args = ap.parse_args()

    from benchmarks import (ablation_ddrf, accel_bench, analysis_bench,
                            async_gossip_bench, chebyshev_bench, comm_costs,
                            convergence_curve, kernel_bench, multiout_bench,
                            paper_fig1_noniid_y, paper_fig2_noniid_xnorm,
                            paper_fig3_imbalanced, paper_fig4_pernode,
                            paper_table2, roofline, serve_bench, solve_bench,
                            step_kernel_bench, stream_bench)
    from repro.compile_cache import enable_compile_cache
    from repro.obs.export import provenance, stamp_provenance, write_jsonl
    from repro.obs.metrics import Registry, perf_clock
    from repro.obs.spans import recording, span

    enable_compile_cache()

    suites = {
        "table2": paper_table2.run,
        "fig1": paper_fig1_noniid_y.run,
        "fig2": paper_fig2_noniid_xnorm.run,
        "fig3": paper_fig3_imbalanced.run,
        "fig4": paper_fig4_pernode.run,
        "comm": comm_costs.run,
        "convergence": convergence_curve.run,
        "ablation": ablation_ddrf.run,
        "chebyshev": chebyshev_bench.run,
        "accel": accel_bench.run,
        "kernel": kernel_bench.run,
        "step": step_kernel_bench.run,
        "solve": solve_bench.run,
        "async": async_gossip_bench.run,
        "multiout": multiout_bench.run,
        "stream": stream_bench.run,
        "serve": serve_bench.run,
        "roofline": roofline.run,
        "analysis": analysis_bench.run,
    }
    registry = Registry(clock=perf_clock)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    print("name,us_per_call,derived")
    failed = []
    with recording(registry):
        for name, fn in suites.items():
            if args.only and name != args.only:
                continue
            t0 = perf_clock()
            try:
                with span(f"bench.{name}", fast=bool(args.fast)):
                    fn(fast=args.fast)
            except Exception as e:  # noqa: BLE001 — run every suite
                failed.append((name, repr(e)))
                traceback.print_exc()
                registry.counter("bench.suites_failed").inc()
                print(f"{name}/FAILED,0.0,{e!r}")
            dt = perf_clock() - t0
            registry.counter("bench.suites_run").inc()
            registry.histogram("bench.suite_seconds").observe(dt)
            print(f"{name}/total,{dt*1e6:.0f},done", flush=True)
    if args.profile_dir:
        jax.profiler.stop_trace()
    prov = provenance(interpret=jax.default_backend() == "cpu",
                      extra={"fast": bool(args.fast), "only": args.only})
    stamped = [p for p in sorted(glob.glob(os.path.join(REPO_ROOT,
                                                        "BENCH_*.json")))
               if stamp_provenance(p, prov)]
    if stamped:
        print(f"stamped provenance into {len(stamped)} artifact(s)",
              file=sys.stderr)
    if args.obs_jsonl:
        write_jsonl(registry, args.obs_jsonl, prov)
        print(f"telemetry written to {args.obs_jsonl}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
