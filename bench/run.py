"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `BENCHMARK.json`; its configuration, traffic mix,
generator and per-layer metrics are found by name (`bench.registry`). The
run sets up (data from the configuration, keys and arrivals from
`--seed`, every shape warmed), measures for `--seconds`, then judges what
the window produced against the plain reference. With `--trace 0` it
reports the cell's end-to-end metrics; with `--trace 1` it records the
window with the profiler and reports the per-layer metrics.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device, (breakdown,) checks. The numbers compared, each
beside its limit, are also the last lines of standard error. Without a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.
"""
from __future__ import annotations

import os
import sys
import time


def _process_age() -> float:
    """Seconds since this process started (Linux), else 0."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


T_PROCESS = time.perf_counter() - _process_age()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

    from bench import harness

    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), root=ROOT, t_process=T_PROCESS,
                       trace_dir=TRACE_DIR)


if __name__ == "__main__":
    sys.exit(main())
