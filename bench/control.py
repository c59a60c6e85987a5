"""Readings that set the limits of `correct`: sound runs of the program,
and the control, on the chip at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 ... \
        --control-seeds 101 102 103

For each seed of `--seeds` it judges what the program's timed path
produces (a fit, an SPMD solve, a wave of served answers) and prints the
numbers; with `--fault <name>` (`bench.faults`) the same runs are made
with that fault planted underneath. For each of `--control-seeds` it
puts the reference in the program's place, computed in float32 with
every matrix product as three bf16 passes (the precision a TPU's
`Precision.HIGH` gives, one step below the configuration's float32 at
full precision), and judges that the same way. One JSON line per
reading. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL = "float32_3pass"


def control_fit(dep, keys):
    """The reference in the program's place: DDRF, Eq. 17 and Eq. 19 to
    tol, all in the control precision, on the default device. Returns
    what the judge reads of a fit."""
    import jax.numpy as jnp
    import numpy as np

    from bench import check, reference as R

    cfg = dep.config
    cands = check.candidates(dep, keys)
    om, bi = [], []
    for j, (co, cb) in enumerate(cands):
        s = np.asarray(R.energy_scores(
            jnp.asarray(co), jnp.asarray(cb), jnp.asarray(dep.x_train[j]),
            jnp.asarray(dep.y_train[j]), precision=CONTROL))
        keep = np.argsort(-s)[:dep.widths[j]]
        om.append(co[keep])
        bi.append(cb[keep])
    blocks = R.eq17_blocks(om, bi, dep.x_train, dep.y_train, dep.adjacency,
                           cfg["lam"], cfg["c_nei_over_n"] * dep.num_train,
                           cfg["c_self_ratio"], CONTROL)
    theta, stop, _ = R.eq19_rounds(blocks, None, tol=cfg["tol"],
                                   precision=CONTROL,
                                   budget=cfg["round_budget"])
    return keys, om, bi, theta, cfg["round_budget"] if stop is None else stop


def control_serve(cell, seed, queries: int):
    """The reference's answers in the control precision to `queries`
    test-split queries, against the weights the cell serves at `seed`."""
    import jax
    import numpy as np

    from bench import check, deploy, reference as R
    from bench.registry import seed32

    dep = deploy.build(cell.config)
    drv = cell.generator
    weights = [tuple(np.asarray(a) for a in w) for w in drv.make_weights(
        jax.random.PRNGKey(seed32(seed)), cell.config["sigma"],
        cell.traffic["theta_std"], widths=tuple(dep.widths), dim=dep.dim)]
    pool = np.concatenate(dep.x_test, axis=1)
    x = pool[:, np.random.default_rng([seed, 3]).integers(
        0, pool.shape[1], queries)]
    got = np.asarray(R.predict([w for w, _, _ in weights],
                               [b for _, b, _ in weights],
                               [t for _, _, t in weights], x, CONTROL))
    return check.judge_answers([w for w, _, _ in weights],
                               [b for _, b, _ in weights],
                               [t for _, _, t in weights], x, got)


def main() -> int:
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import faults

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of each program reading")
    ap.add_argument("--fault", choices=faults.NAMES,
                    help="plant this fault under the program's runs")
    ap.add_argument("--problems", type=int, default=0,
                    help="solve cells: packed problems to build and judge")
    args = ap.parse_args()
    import jax

    from bench import registry
    from bench.harness import Phases, enable_cache, require_chips

    cell = registry.load_cell(args.workload, ROOT)
    require_chips(cell.chips)
    enable_cache()
    kind = cell.traffic["generator"]
    drv = cell.generator
    if args.problems:
        cell.traffic.update(problems=args.problems,
                            check_solves=args.problems)

    def emit(tag, seed, numbers, t0):
        print(json.dumps({"workload": args.workload, "run": tag,
                          "seed": seed, "seconds": time.perf_counter() - t0,
                          **numbers}), flush=True)

    tag = "fault:" + args.fault if args.fault else "program"
    for seed in args.seeds:
        t0 = time.perf_counter()
        with faults.planted(args.fault):
            state = drv.setup(cell, seed, Phases(False))
            result = drv.window(state, args.seconds, Phases(False))
            sample = drv.check_outputs(state)
        for numbers in drv.judge(state, sample):
            emit(tag, seed, dict(numbers, failed=result["failed"]), t0)
    for seed in args.control_seeds:
        t0 = time.perf_counter()
        if kind == "open_loop_serve":
            emit("control", seed, control_serve(cell, seed, 20000), t0)
            continue
        from bench import check, deploy
        from bench.registry import seed32

        dep = deploy.build(cell.config)
        state = {"dep": dep}
        if kind == "fit_loop":
            state["key"] = jax.random.PRNGKey(seed32(seed))
            keys = drv.node_keys(state, 0)
        else:                                # seed = the problem's index
            state["key"] = jax.random.PRNGKey(cell.config["data_seed"])
            keys = drv.node_keys(state, seed)
        with jax.default_device(jax.devices()[0]):
            item = control_fit(dep, keys)
        emit("control", seed, check.judge_fit(dep, *item), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
