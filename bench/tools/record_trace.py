"""Record a traced window of a cell and write what the trace holds.

    python3 bench/tools/record_trace.py --workload <cell> --seed <n> \
        --seconds <s> --out out/trace.json

Writes the planes and lines of the profiler trace with their event
counts, the most frequent op names per device, and an excerpt of the
window's first `--excerpt-ms` milliseconds (device ops and bench host
spans, on the trace's clock) that the CPU tests of the trace reduction
use as a recorded trace.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--excerpt-ms", type=float, default=40.0)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax

    from bench import registry, trace as btrace
    from bench.harness import Phases, enable_cache, require_chips

    cell = registry.load_cell(args.workload, ROOT)
    require_chips(cell.chips)
    enable_cache()
    state = cell.generator.setup(cell, args.seed, Phases(False))
    tdir = os.path.join(ROOT, ".bench_trace_record")
    with jax.profiler.trace(tdir,
                            profiler_options=btrace.profiler_options()):
        with jax.profiler.TraceAnnotation("bench.window"):
            cell.generator.window(state, args.seconds, Phases(True))
    path = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    data = jax.profiler.ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            lines[line.name] = {"events": sum(names.values()),
                                "top": names.most_common(12)}
        planes[plane.name] = lines
    tr = btrace.load(tdir)
    lo, _ = tr.window()
    hi = lo + int(args.excerpt_ms * 1e6)
    excerpt = {
        "ops": {d: [[n, s, e] for n, s, e in ops if s < hi and e > lo]
                for d, ops in tr.ops.items()},
        "spans": [[n, s, min(e, hi), {}] for n, s, e, _ in tr.spans
                  if s < hi and e > lo and n != "bench.window"]
                 + [["bench.window", lo, hi, {}]],
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"planes": planes, "excerpt": excerpt,
                   "size_bytes": os.path.getsize(path)}, f)
    print(json.dumps({p: {ln: v["events"] for ln, v in lines.items()}
                      for p, lines in planes.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
