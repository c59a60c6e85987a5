"""Open-loop Poisson arrivals of single-point queries, answered as the
network-average predictor by the program's replica server.

Set-up publishes one snapshot through the program's `SnapshotRegistry`:
per node, D_j frequencies ω ~ N(0, σ⁻²I), phases b ~ U[0, 2π) and
coefficients θ ~ N(0, theta_std²), made on the device in one jitted call
from the seed (serving costs the same whatever θ holds, and the reference
then judges against weights the benchmark made). Queries are drawn
uniformly from the deployment's held-out test split. Every pad bucket
the window's waves can use is warmed by a wave of that width.

Each query is timed from when it was due to when the server marked it
done; the generator passes the due time as the admission time. Traffic
parameters: "rate_qps" (the fixed offered rate), "theta_std",
"warm_waves" (the wave widths that cover every pad bucket).
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, deploy
from bench.latency import OpenLoop, percentile, poisson_arrivals
from bench.registry import seed32

DRAIN_S = 60.0          # how long past the window's close an answer may come


@partial(jax.jit, static_argnames=("widths", "dim"))
def make_weights(key, sigma, theta_std, *, widths, dim):
    """Per node (ω [D_j, d], b [D_j], θ [D_j]) in float32, in one call."""
    out = []
    for j, dj in enumerate(widths):
        k_w, k_b, k_t = jax.random.split(jax.random.fold_in(key, j), 3)
        out.append((jax.random.normal(k_w, (dj, dim), jnp.float32) / sigma,
                    jax.random.uniform(k_b, (dj,), jnp.float32,
                                       maxval=2 * jnp.pi),
                    jax.random.normal(k_t, (dj,), jnp.float32) * theta_std))
    return out


def _query_class():
    from repro.serve.dekrr import KernelQuery

    class TimedQuery(KernelQuery):
        """A query that reports the instant the server marks it done."""

        def __setattr__(self, name, value):
            object.__setattr__(self, name, value)
            if name == "done" and value:
                self.on_done()

    return TimedQuery


def setup(cell, seed: int, phases) -> dict:
    from repro.core.rff import FeatureMap
    from repro.serve.dekrr import DeKRRReplicaServer
    from repro.stream import SnapshotRegistry
    from repro.stream.runtime import ServeSnapshot, StalenessBound

    with phases("data"):
        dep = deploy.build(cell.config)
    tr = cell.traffic
    with phases("weights"):
        weights = jax.block_until_ready(make_weights(
            jax.random.PRNGKey(seed32(seed)), cell.config["sigma"],
            tr["theta_std"], widths=tuple(dep.widths), dim=dep.dim))
    snap = ServeSnapshot(
        feature_maps=tuple(FeatureMap(omega=w, bias=b, kind="cos_bias")
                           for w, b, _ in weights),
        theta=tuple(t for _, _, t in weights),
        staleness=StalenessBound(theta_version=1, ingests_behind=0,
                                 samples_behind=0, residual=0.0))
    registry = SnapshotRegistry()
    registry.publish(snap)
    server = DeKRRReplicaServer(registry)
    pool = np.concatenate(dep.x_test, axis=1)
    state = dict(dep=dep, traffic=tr, seed=seed, server=server,
                 weights=weights, pool=pool, query=_query_class())
    rng = np.random.default_rng([seed, 2])
    with phases("warm"):
        for width in tr["warm_waves"]:
            qs = _queries(state,
                          pool[:, rng.integers(0, pool.shape[1], width)],
                          lambda i: None)
            server.run(qs)
    return state


def _queries(state, x, on_done):
    qs = []
    for i in range(x.shape[1]):
        q = state["query"](uid=i, x=x[:, i])
        object.__setattr__(q, "on_done", partial(on_done, i))
        qs.append(q)
    return qs


def window(state, seconds: float, phases) -> dict:
    server, pool = state["server"], state["pool"]
    rng = np.random.default_rng([state["seed"], 3])
    offsets = poisson_arrivals(state["traffic"]["rate_qps"], seconds, rng)
    x = pool[:, rng.integers(0, pool.shape[1], offsets.shape[0])]
    loop = OpenLoop()
    queries = _queries(state, x, loop.complete)
    waves0 = server.waves_served
    server.start()
    try:
        with phases("generate"):
            t0 = loop.run(offsets,
                          lambda i, due: server.submit(queries[i], now=due))
        with phases("drain"):
            deadline = t0 + seconds + DRAIN_S
            while loop.pending() and time.perf_counter() < deadline:
                time.sleep(0.001)
    finally:
        unanswered = loop.pending()
        if not unanswered:
            server.stop()
    lat = loop.latencies() * 1e3
    state.update(queries=queries, x=x)
    return {"attempted": len(queries), "failed": unanswered,
            "window_s": seconds,
            "end_to_end": {"answer_p50_ms": percentile(lat, 50),
                           "answer_p99_ms": percentile(lat, 99)},
            "counts": {"answered": int(lat.size),
                       "gen_lag_p99_ms": percentile(loop.lags() * 1e3, 99),
                       "waves": server.waves_served - waves0}}


def check_outputs(state) -> list:
    """Every answered query of the window with the weights it was served
    from, read to the host; the server is dropped."""
    queries, x = state.pop("queries"), state.pop("x")
    done = [i for i, q in enumerate(queries) if q.done]
    answers = np.array([float(queries[i].prediction) for i in done])
    weights = [tuple(np.asarray(a) for a in w) for w in state.pop("weights")]
    state.pop("server")
    return [(weights, x[:, done], answers)]


def judge(state, sample) -> list[dict]:
    return [check.judge_answers([w for w, _, _ in weights],
                                [b for _, b, _ in weights],
                                [t for _, _, t in weights], xq, answers)
            for weights, xq, answers in sample]
