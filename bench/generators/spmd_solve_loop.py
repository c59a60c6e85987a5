"""Closed loop of SPMD solves to tol, one node per chip.

Set-up fits `problems` packed problems the program's normal way (DDRF per
node from fold_in(key(data_seed), p), `pack_problem`), places each on the
node mesh (node j's blocks on chip j) and warms one solve of each. Every
seed solves the same problems, so that rounds to tol, which differ from
one DDRF draw to the next, do not differ from one seed to the next; the
seed sets the order the window cycles through them. Each solve runs `make_spmd_solver(mesh,
"nodes")` from θ = 0 to tol and ends in `block_until_ready`. Per round
θ moves between chips and a network-wide max decides the stop. Traffic
parameters: "problems", "check_solves" (of how many problems the
reference judges one window solve, drawn from the seed).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import check, deploy


def node_keys(state, p: int) -> list:
    return deploy.node_keys(state["key"], p, state["dep"].num_nodes)


def setup(cell, seed: int, phases) -> dict:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.dist import make_spmd_solver

    cfg = cell.config
    with phases("data"):
        dep = deploy.build(cfg)
    state = dict(dep=dep, cfg=cfg, traffic=cell.traffic, seed=seed,
                 key=jax.random.PRNGKey(cfg["data_seed"]))
    mesh = Mesh(np.array(jax.devices()[:dep.num_nodes]), ("nodes",))
    on_mesh = NamedSharding(mesh, PartitionSpec("nodes"))
    with phases("problems"):
        state["problems"] = [fit_problem(state, p, on_mesh) for p in
                             range(int(cell.traffic["problems"]))]
    state["solve"] = make_spmd_solver(mesh, "nodes")
    with phases("warm"):
        for p in range(len(state["problems"])):
            jax.block_until_ready(solve(state, p))
    return state


def fit_problem(state, p: int, on_mesh):
    """Problem p the program's normal way, its blocks placed node j on
    chip j: (feature maps, packed problem)."""
    from repro.core import (DeKRRConfig, DeKRRSolver, NodeData, circulant,
                            select_features)
    from repro.dist import pack_problem

    dep, cfg = state["dep"], state["cfg"]
    keys = node_keys(state, p)
    fmaps = [select_features(keys[j], dep.dim, dep.widths[j], cfg["sigma"],
                             dep.x_train[j], dep.y_train[j],
                             method=cfg["ddrf_method"],
                             candidate_ratio=cfg["candidate_ratio"])
             for j in range(dep.num_nodes)]
    solver = DeKRRSolver(
        circulant(cfg["num_nodes"], cfg["graph"]["offsets"]), fmaps,
        [NodeData(x=x, y=y) for x, y in zip(dep.x_train, dep.y_train)],
        DeKRRConfig(lam=cfg["lam"],
                    c_nei=cfg["c_nei_over_n"] * dep.num_train),
        build_aux=False)
    return fmaps, jax.device_put(pack_problem(solver), on_mesh)


def solve(state, p: int):
    return state["solve"](state["problems"][p][1],
                          state["cfg"]["round_budget"],
                          tol=state["cfg"]["tol"], return_rounds=True)


def window(state, seconds: float, phases) -> dict:
    solves, failed = [], 0
    order = np.random.default_rng([state["seed"], 4]).permutation(
        len(state["problems"]))
    t0 = time.perf_counter()
    t_end, i = t0, 0
    while t_end - t0 < seconds:
        p = int(order[i % len(order)])
        try:
            with phases("solve"):
                solves.append((p, jax.block_until_ready(solve(state, p))))
        except Exception as exc:            # counted, and it fails the run
            failed += 1
            print(f"solve {i} failed: {exc!r}")
        t_end = time.perf_counter()
        i += 1
    state["solves"] = solves
    n = len(solves)
    rounds = [int(r) for _, (_, r) in solves]
    return {"attempted": i, "failed": failed, "window_s": t_end - t0,
            "end_to_end": {"solve_s": (t_end - t0) / n if n
                           else float("inf")},
            "counts": {"solves": n, "rounds_mean": float(np.mean(rounds))
                       if rounds else float("nan")}}


def check_outputs(state) -> list[tuple]:
    """The window solves the reference judges, on the host: one solve of
    each of `check_solves` problems drawn from the seed. The program's
    problems are dropped."""
    solves = state.pop("solves")
    dep = state["dep"]
    rng = np.random.default_rng([state["seed"], 1])
    by_problem: dict[int, list] = {}
    for p, out in solves:
        by_problem.setdefault(p, []).append(out)
    problems = sorted(by_problem)
    k = min(int(state["traffic"]["check_solves"]), len(problems))
    out = []
    for p in sorted(rng.choice(problems, size=k, replace=False)) if k else []:
        runs = by_problem[int(p)]
        theta, rounds = runs[int(rng.integers(len(runs)))]
        fmaps = state["problems"][int(p)][0]
        th = np.asarray(theta)
        out.append((node_keys(state, int(p)),
                    [np.asarray(f.omega) for f in fmaps],
                    [np.asarray(f.bias) for f in fmaps],
                    [th[j, :dep.widths[j]] for j in range(dep.num_nodes)],
                    int(rounds)))
    state.pop("problems")
    state.pop("solve")
    return out


def judge(state, sample) -> list[dict]:
    return [check.judge_fit(state["dep"], *item) for item in sample]
