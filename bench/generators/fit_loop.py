"""Closed loop of back-to-back fits, each from node data on the host to θ
at tol on the device.

One fit is the program's normal path with the deployment's parameters:

    select_features per node (DDRF, energy score)
      → DeKRRSolver(build_aux=False) → pack_problem → solve_batched(tol)
      → block_until_ready

Fit i draws its DDRF candidates from fold_in(key(seed), i), node j's from
fold_in of that with j, so no fit can reuse another's result. Traffic
parameters: "check_fits", how many fits of the window the reference
judges (drawn from the seed).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import check, deploy
from bench.registry import seed32

WARM_FIT = 0x7FFFFFFF       # the set-up fit's index, never a window fit's


def setup(cell, seed: int, phases) -> dict:
    from repro.core import NodeData, circulant

    with phases("data"):
        dep = deploy.build(cell.config)
    cfg = cell.config
    nodes = [NodeData(x=x, y=y) for x, y in zip(dep.x_train, dep.y_train)]
    state = dict(dep=dep, nodes=nodes, cfg=cfg, traffic=cell.traffic,
                 key=jax.random.PRNGKey(seed32(seed)), seed=seed,
                 topology=circulant(cfg["num_nodes"],
                                    cfg["graph"]["offsets"]))
    with phases("warm"):
        jax.block_until_ready(fit(state, WARM_FIT, phases))
    return state


def node_keys(state, i: int) -> list:
    return deploy.node_keys(state["key"], i, state["dep"].num_nodes)


def fit(state, i: int, phases):
    """One fit; returns (feature maps, θ [J, D_max], rounds)."""
    from repro.core import DeKRRConfig, DeKRRSolver, select_features
    from repro.dist import pack_problem, solve_batched

    dep, cfg = state["dep"], state["cfg"]
    keys = node_keys(state, i)
    with phases("ddrf"):
        fmaps = [select_features(keys[j], dep.dim, dep.widths[j],
                                 cfg["sigma"], dep.x_train[j],
                                 dep.y_train[j], method=cfg["ddrf_method"],
                                 candidate_ratio=cfg["candidate_ratio"])
                 for j in range(dep.num_nodes)]
        phases.sync(fmaps)
    with phases("pack"):
        solver = DeKRRSolver(
            state["topology"], fmaps, state["nodes"],
            DeKRRConfig(lam=cfg["lam"],
                        c_nei=cfg["c_nei_over_n"] * dep.num_train),
            build_aux=False)
        packed = pack_problem(solver)
        phases.sync(packed)
    with phases("solve"):
        theta, rounds = solve_batched(packed, cfg["round_budget"],
                                      tol=cfg["tol"], return_rounds=True)
        phases.sync(theta)
    return fmaps, theta, rounds


def window(state, seconds: float, phases) -> dict:
    fits, failed = [], 0
    t0 = time.perf_counter()
    t_end = t0
    i = 0
    while t_end - t0 < seconds:
        try:
            out = jax.block_until_ready(fit(state, i, phases))
            fits.append((i, out))
        except Exception as exc:            # counted, and it fails the run
            failed += 1
            print(f"fit {i} failed: {exc!r}")
        t_end = time.perf_counter()
        i += 1
    state["fits"] = fits
    n = len(fits)
    rounds = [int(out[2]) for _, out in fits]
    return {"attempted": i, "failed": failed, "window_s": t_end - t0,
            "end_to_end": {"fit_s": (t_end - t0) / n if n else float("inf")},
            "counts": {"fits": n, "rounds_mean": float(np.mean(rounds))
                       if rounds else float("nan")}}


def outputs(state, i: int, out) -> tuple:
    """What the judge reads of one fit, on the host."""
    fmaps, theta, rounds = out
    dep = state["dep"]
    th = np.asarray(theta)
    return (node_keys(state, i),
            [np.asarray(f.omega) for f in fmaps],
            [np.asarray(f.bias) for f in fmaps],
            [th[j, :dep.widths[j]] for j in range(dep.num_nodes)],
            int(rounds))


def check_outputs(state) -> list[tuple]:
    """The sample of window fits the reference judges, read to the host,
    after which the program's state is dropped."""
    fits = state.pop("fits")
    rng = np.random.default_rng([state["seed"], 1])
    k = min(int(state["traffic"]["check_fits"]), len(fits))
    pick = sorted(rng.choice(len(fits), size=k, replace=False)) if k else []
    out = [outputs(state, *fits[p]) for p in pick]
    for key in ("nodes", "topology"):
        state.pop(key)
    return out


def judge(state, sample) -> list[dict]:
    return [check.judge_fit(state["dep"], *item) for item in sample]
