"""Choose σ and c for a deployment by the paper's protocol, on the CPU.

    python3 bench/tools/choose_sigma_c.py --nodes 10 --offsets 1 2 \
        --partition noniid_y --sizes equal --dbar 130

The paper (§IV-A) picks σ and c by validation. Here: float64 reference,
DDRF features from key fold_in(PRNGKey(0), j) on each node's training
half, a 25% validation slice of each node's training half (seed 0), each
node's own predictor on its own validation slice, RSE over the pooled
slices. The chosen pair is then refitted on the full training halves and
reported with its test RSE, ρ(M) and max|θ*|. σ ∈ {2^-2 … 2^2} and
c/N ∈ {0.002, 0.01, 0.05, 0.5, 2.0} (the program's `CV_SIGMA` and
`CV_C_NEI_EXTENDED` grids), λ = 1e-6.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import data, reference as R  # noqa: E402
from bench.registry import adjacency_of, feature_widths  # noqa: E402

SIGMAS = tuple(2.0 ** i for i in range(-2, 3))
C_OVER_N = (0.002, 0.01, 0.05, 0.5, 2.0)
LAM, C_SELF_RATIO, RATIO = 1e-6, 5.0, 20


def rse(pred, y):
    return float(np.sum((pred - y) ** 2) / np.sum((y - y.mean()) ** 2))


def select(key, x, y, width, sigma):
    om, b = R.draw_candidates(key, x.shape[0], RATIO * width, sigma)
    s = np.asarray(R.energy_scores(jnp.asarray(om, jnp.float64),
                                   jnp.asarray(b, jnp.float64),
                                   jnp.asarray(x), jnp.asarray(y),
                                   precision="float64"))
    keep = np.argsort(-s)[:width]
    return om[keep].astype(np.float64), b[keep].astype(np.float64)


def fit_and_score(maps, xs, ys, xs_eval, ys_eval, adj, sigma, c):
    om, bi = zip(*maps)
    n = sum(x.shape[1] for x in xs)
    blocks = R.eq17_blocks(om, bi, xs, ys, adj, LAM, c * n, C_SELF_RATIO,
                           "float64")
    theta = R.exact_theta(blocks)
    pred = np.concatenate([
        np.asarray(R.predict([om[j]], [bi[j]], [theta[j]], xs_eval[j],
                             "float64")) for j in range(len(xs))])
    return rse(pred, np.concatenate(ys_eval)), blocks, theta


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", type=int, default=10)
    ap.add_argument("--offsets", type=int, nargs="+", default=[1, 2])
    ap.add_argument("--partition", default="noniid_y")
    ap.add_argument("--sizes", default="equal")
    ap.add_argument("--dbar", type=int, default=130)
    ap.add_argument("--widths", default="equal")
    ap.add_argument("--sigmas", type=float, nargs="+", default=SIGMAS)
    args = ap.parse_args()
    jax.config.update("jax_enable_x64", True)
    adj = adjacency_of({"kind": "circulant", "offsets": args.offsets},
                       args.nodes)
    x, y = data.make_dataset("twitter", seed=0)
    tr, te = data.node_shards(y, args.nodes, args.partition, args.sizes)
    widths = feature_widths(args.widths, args.dbar,
                            [len(i) for i in tr])
    rng = np.random.default_rng(0)
    fit_idx, val_idx = [], []
    for idx in tr:
        perm = rng.permutation(len(idx))
        k = max(int(len(idx) * 0.25), 1)
        val_idx.append(idx[perm[:k]])
        fit_idx.append(idx[perm[k:]])
    xs = lambda ii: [x[:, i] for i in ii]
    ys = lambda ii: [y[i] for i in ii]
    rows = []
    for sigma in args.sigmas:
        keys = [jax.random.fold_in(jax.random.PRNGKey(0), j)
                for j in range(args.nodes)]
        maps = [select(keys[j], x[:, tr[j]], y[tr[j]], widths[j], sigma)
                for j in range(args.nodes)]
        for c in C_OVER_N:
            v, _, _ = fit_and_score(maps, xs(fit_idx), ys(fit_idx),
                                    xs(val_idx), ys(val_idx), adj, sigma, c)
            rows.append((v, sigma, c))
            print(f"sigma={sigma} c/N={c} validation RSE {v:.5f}",
                  flush=True)
    v, sigma, c = min(rows)
    keys = [jax.random.fold_in(jax.random.PRNGKey(0), j)
            for j in range(args.nodes)]
    maps = [select(keys[j], x[:, tr[j]], y[tr[j]], widths[j], sigma)
            for j in range(args.nodes)]
    t, blocks, theta = fit_and_score(maps, xs(tr), ys(tr), xs(te), ys(te),
                                     adj, sigma, c)
    print(json.dumps({
        "sigma": sigma, "c_nei_over_n": c, "validation_rse": v,
        "test_rse": t, "rho": R.spectral_radius(blocks),
        "max_abs_theta": float(max(np.max(np.abs(th)) for th in theta)),
        "widths": widths}))


if __name__ == "__main__":
    main()
