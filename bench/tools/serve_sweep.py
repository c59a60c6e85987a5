"""Find the serve cell's knee once: the highest offered rate at which the
answer p99 stays within its limit and the backlog does not grow.

    python3 bench/tools/serve_sweep.py --workload table2-twitter.serve \
        --seed <n> --seconds 5 --rates 500 1000 2000 4000 --p99-limit-ms 50

Sets the cell up once, then offers each rate for `--seconds` through the
cell's own open-loop window and prints one JSON line per rate: p50, p99,
the generator's p99 lag, and the backlog (queries due but unanswered)
when the generator finished.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--p99-limit-ms", type=float, default=50.0)
    args = ap.parse_args()
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import registry
    from bench.harness import Phases, enable_cache, require_chips

    cell = registry.load_cell(args.workload, ROOT)
    require_chips(cell.chips)
    enable_cache()
    drv = cell.generator
    state = drv.setup(cell, args.seed, Phases(False))
    for rate in args.rates:
        state["traffic"] = dict(state["traffic"], rate_qps=rate)
        phases = Phases(False)
        result = drv.window(state, args.seconds, phases)
        gen = [s for s in phases.spans if s[0] == "generate"][0]
        drain = [s for s in phases.spans if s[0] == "drain"][0]
        c, e = result["counts"], result["end_to_end"]
        print(json.dumps({
            "rate_qps": rate, "answered": c["answered"],
            "failed": result["failed"], "p50_ms": e["answer_p50_ms"],
            "p99_ms": e["answer_p99_ms"], "gen_lag_p99_ms":
                c["gen_lag_p99_ms"], "waves": c["waves"],
            "generate_s": gen[2] - gen[1], "drain_s": drain[2] - drain[1],
            "holds": e["answer_p99_ms"] <= args.p99_limit_ms
                and drain[2] - drain[1] < 0.05}), flush=True)
        state.pop("queries", None)
        state.pop("x", None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
