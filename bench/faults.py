"""Faults planted underneath a cell's timed path, to read what the check
makes of them.

    with faults.planted("stop_round1"):
        ... run the cell ...

Each fault breaks one thing the program guarantees, where it is
produced, and leaves the rest of the path as it is:

  state_unchanged   the solve returns θ = 0, its starting state;
  answer_altered    one coefficient of the solve's θ moved by 5% of max|θ|;
  stop_round1       the solve (batched or SPMD) stops after its first
                    round;
  stop_10tol        the solve (batched or SPMD) stops at 10× the
                    configuration's tol;
  no_exchange       the SPMD solve's exchange between chips left out:
                    every neighbour's θ reads 0;
  ddrf_random       DDRF keeps D_j of its own candidates drawn at random,
                    not by the energy score;
  half_batch        each node's Eq. 17 blocks built from half its data;
  serve_altered     every served answer scaled by 1.001;
  serve_half_nodes  a wave answers only the first half of its queries.

`bench/control.py --fault` reads them on the chip; `tests/bench` plants
them at a tiny size on the CPU.
"""
from __future__ import annotations

import contextlib

FIT = ("state_unchanged", "answer_altered", "stop_round1", "stop_10tol",
       "ddrf_random", "half_batch", "no_exchange")
SERVE = ("serve_altered", "serve_half_nodes")
NAMES = FIT + SERVE


def _solve(transform):
    """The batched solve, and the SPMD solver's runner, with each call
    passed through `transform(packed, num_iters, args, kw, real)`."""
    import repro.dist

    real_batched = repro.dist.solve_batched
    real_spmd = repro.dist.make_spmd_solver

    def solve(packed, num_iters, *args, **kw):
        return transform(packed, num_iters, args, kw, real_batched)

    def make_spmd_solver(*args, **kw):
        run = real_spmd(*args, **kw)
        return lambda packed, num_iters, *a, **k: transform(
            packed, num_iters, a, k, run)

    return [(repro.dist, "solve_batched", solve),
            (repro.dist, "make_spmd_solver", make_spmd_solver)]


def _patch(name: str):
    """[(module, attribute, replacement)] of fault `name`."""
    import jax
    import jax.numpy as jnp

    if name == "state_unchanged":
        def t(pk, n, a, kw, real):
            theta, rounds = real(pk, n, *a, **kw)
            return jnp.zeros_like(theta), rounds
        return _solve(t)
    if name == "answer_altered":
        def t(pk, n, a, kw, real):
            theta, rounds = real(pk, n, *a, **kw)
            return theta.at[0, 0].add(0.05 * jnp.max(jnp.abs(theta))), rounds
        return _solve(t)
    if name == "stop_round1":
        return _solve(lambda pk, n, a, kw, real: real(pk, 1, *a, **kw))
    if name == "stop_10tol":
        return _solve(lambda pk, n, a, kw, real: real(
            pk, n, *a, **dict(kw, tol=10 * kw["tol"])))
    if name == "ddrf_random":
        import repro.core.ddrf as ddrf

        real = ddrf.energy_scores

        def scores(cand, x, y):
            s = real(cand, x, y)
            return jax.random.uniform(jax.random.PRNGKey(s.shape[0]),
                                      s.shape, s.dtype)
        return [(ddrf, "energy_scores", scores)]
    if name == "half_batch":
        import repro.dist
        from repro.core import DeKRRSolver, NodeData

        real = repro.dist.pack_problem

        def pack(solver, **kw):
            half = [NodeData(x=nd.x[:, :nd.num_samples // 2],
                             y=nd.y[:nd.num_samples // 2])
                    for nd in solver.data]
            return real(DeKRRSolver(solver.topology, solver.feature_maps,
                                    half, solver.config, build_aux=False),
                        **kw)
        return [(repro.dist, "pack_problem", pack)]
    if name in SERVE:
        import repro.serve.dekrr as serve

        real = serve.answer_wave

        def wave(st, x):
            preds, bounds = real(st, x)
            if name == "serve_altered":
                return preds * 1.001, bounds
            return preds[:preds.shape[0] // 2], bounds
        return [(serve, "answer_wave", wave)]
    if name == "no_exchange":
        import repro.dist.dekrr_spmd as spmd

        real = spmd._make_exchange

        def exchange(*args, **kw):
            send = real(*args, **kw)
            return lambda vec: jnp.zeros_like(send(vec))
        return [(spmd, "_make_exchange", exchange)]
    raise ValueError(f"unknown fault {name!r}; have {NAMES}")


@contextlib.contextmanager
def planted(name: str | None):
    """Fault `name` in place for the body (None plants nothing)."""
    if name is None:
        yield
        return
    patches = _patch(name)
    reals = [getattr(module, attr) for module, attr, _ in patches]
    for module, attr, fake in patches:
        setattr(module, attr, fake)
    try:
        yield
    finally:
        for (module, attr, _), real in zip(patches, reals):
            setattr(module, attr, real)
