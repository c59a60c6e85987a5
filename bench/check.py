"""The comparisons that decide `correct`, and their numbers.

A fit (or a solve of a fitted problem) is judged by the plain reference
(`bench.reference`) in float64 on the host CPU:

  ddrf_unmatched     selected feature rows that are not rows of the
                     node's own DDRF candidate draw (or repeat one);
  ddrf_energy_loss   worst node's 1 − (energy score of the selected rows)
                     / (energy score of the reference's top D_j);
  theta_rel_err      max|θ − θ_ref| / max|θ_ref|, θ_ref being the
                     reference's Eq. 19 iterate after as many rounds as
                     the run reports, on the selected features;
  theta_stop_err     the same against the reference's iterate at its own
                     stop, the first round whose max|Δθ| falls below tol:
                     a run that stops early or late reads high here.

A served answer is judged by the reference's network-average answer:
  answer_rel_err     max|a − a_ref| / max|a_ref| over the answers judged.

Each number's limit comes from the configuration's "limits".
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R


def _host():
    """float64 on the host CPU, whatever the default device is."""
    import contextlib

    stack = contextlib.ExitStack()
    stack.enter_context(jax.enable_x64(True))
    stack.enter_context(jax.default_device(jax.devices("cpu")[0]))
    return stack


def match_rows(omega, bias, cand_omega, cand_bias) -> np.ndarray:
    """Index into the candidates of each selected row, −1 where no
    candidate agrees to float32 rounding or the candidate was taken
    already."""
    om = np.asarray(omega, np.float64)
    co = np.asarray(cand_omega, np.float64)
    dist = (np.sum(om * om, 1)[:, None] + np.sum(co * co, 1)[None, :]
            - 2.0 * om @ co.T)
    idx = np.argmin(dist, axis=1)
    scale = max(1.0, float(np.max(np.abs(co))))
    ok = (np.max(np.abs(om - co[idx]), axis=1) <= 1e-4 * scale) & (
        np.abs(np.asarray(bias, np.float64)
               - np.asarray(cand_bias, np.float64)[idx]) <= 1e-4 * 2 * np.pi)
    out = np.where(ok, idx, -1)
    seen = set()
    for r, i in enumerate(out):
        if i >= 0 and i in seen:
            out[r] = -1
        seen.add(int(i))
    return out


def candidates(dep, node_keys):
    cfg = dep.config
    return [R.draw_candidates(node_keys[j], dep.dim,
                              cfg["candidate_ratio"] * dep.widths[j],
                              cfg["sigma"])
            for j in range(dep.num_nodes)]


def judge_fit(dep, node_keys, omegas, biases, theta, rounds: int) -> dict:
    """The numbers of one fit. omegas/biases: the selected rows per node;
    theta: [D_j] per node; rounds: what the run reports."""
    cfg = dep.config
    cands = candidates(dep, node_keys)
    unmatched, loss, sel = 0, 0.0, []
    with _host():
        for j, (co, cb) in enumerate(cands):
            idx = match_rows(omegas[j], biases[j], co, cb)
            unmatched += int(np.sum(idx < 0))
            scores = np.asarray(R.energy_scores(
                jnp.asarray(co, jnp.float64), jnp.asarray(cb, jnp.float64),
                jnp.asarray(dep.x_train[j], jnp.float64),
                jnp.asarray(dep.y_train[j], jnp.float64),
                precision="float64"))
            best = np.sort(scores)[::-1][:dep.widths[j]].sum()
            loss = max(loss, 1.0 - scores[idx[idx >= 0]].sum() / best)
            sel.append(idx)
        out = {"ddrf_unmatched": float(unmatched), "ddrf_energy_loss": loss}
        if unmatched:
            out.update(theta_rel_err=float("inf"),
                       theta_stop_err=float("inf"))
            return out
        blocks = R.eq17_blocks(
            [cands[j][0][sel[j]] for j in range(dep.num_nodes)],
            [cands[j][1][sel[j]] for j in range(dep.num_nodes)],
            dep.x_train, dep.y_train, dep.adjacency, cfg["lam"],
            cfg["c_nei_over_n"] * dep.num_train, cfg["c_self_ratio"],
            "float64")
        ref, _, at_stop = R.eq19_rounds(blocks, int(rounds), tol=cfg["tol"],
                                        budget=10 * cfg["round_budget"])
    out.update(theta_rel_err=_rel_err(theta, ref),
               theta_stop_err=float("inf") if at_stop is None
               else _rel_err(theta, at_stop))
    return out


def _rel_err(theta, ref) -> float:
    """max|θ − ref| / max|ref| over every node."""
    scale = max(float(np.max(np.abs(r))) for r in ref)
    return max(float(np.max(np.abs(np.asarray(t, np.float64)[:len(r)] - r)))
               for t, r in zip(theta, ref)) / scale


def judge_answers(omegas, biases, thetas, x, answers) -> dict:
    """answer_rel_err of answers [Q] to queries x [d, Q]."""
    with _host():
        want = np.asarray(R.predict(
            [np.asarray(o, np.float64) for o in omegas],
            [np.asarray(b, np.float64) for b in biases],
            [np.asarray(t, np.float64) for t in thetas],
            np.asarray(x, np.float64), "float64"))
    got = np.asarray(answers, np.float64)
    err = float(np.max(np.abs(got - want))) if got.size else float("inf")
    return {"answer_rel_err": err / float(np.max(np.abs(want)))}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(all within limits, {name: {"value", "limit"}}) over the numbers
    the configuration gives a limit; a number that is not finite fails.
    A number with no limit is not compared (see PERF.md for why)."""
    table, ok = {}, True
    for name, value in numbers.items():
        if name not in limits:
            continue
        table[name] = {"value": value, "limit": limits[name]}
        ok = ok and bool(np.isfinite(value) and value <= limits[name])
    return ok, table


def worst(readings: list[dict]) -> dict:
    """The largest reading of each number over several judged items."""
    out: dict = {}
    for r in readings:
        for k, v in r.items():
            out[k] = v if k not in out or not np.isfinite(v) \
                else max(out[k], v)
    return out


def print_table(table: dict, stream=sys.stderr) -> None:
    for name, row in table.items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=stream)
