"""Observability suite (`repro.obs`): on-device traces, host metrics.

The on-device half pins the `return_trace=` contract of every solver:

  * exactness — residuals[r] = max|θ_{r+1} − θ_r| matches a per-round
    host recomputation (via the public single-round steps) at rtol 1e-9
    over {circulant, star, Erdős–Rényi, J=1} × {xla, pallas,
    pallas_fused} × {sync, async}, with the async wire series (active /
    broadcasts / deliveries / bytes) matching the recomputation EXACTLY
    (integer counts) and summing to `AsyncGossipStats`;
  * chunk invariance — `chunk_rounds` ∈ {1, 7, 64} never changes the
    series (bit-for-bit on the fused kernel), and on tol>0 paths every
    executed round's entry equals the tol=0 series with frozen rounds
    recording exactly 0;
  * zero cost — `return_trace=True` adds no pallas_call dispatch
    (`repro.obs.dispatch_count` pins the J002 counts unchanged) and no
    host callback in any loop body (J001), proven by tracing only.

Cross-program comparisons (trace vs a separately compiled
recomputation) use atol=1e-12 alongside rtol=1e-9: deep in convergence
the deltas sit at ~1e-14 where independent compilations differ by ulps.
Same-program claims (fused chunking) are asserted bit-for-bit.

The host-side half unit-tests the metrics/spans/export/report layers
with a `FakeClock` (bit-identical reports), checks the serve-tier
re-exports stayed aliases, and lints the R006 clock chokepoint.
"""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (REPO_ROOT, cached_fmaps, cached_imbalanced,
                      cached_split, subprocess_env)
from repro.core import (AsyncGossipConfig, DeKRRConfig, DeKRRSolver,
                        Topology, circulant, erdos_renyi, star)
from repro.core.acceleration import chebyshev_solve_packed
from repro.core.async_gossip import activation_masks, censor_schedule
from repro.dist import (async_solve_batched, async_step_batched,
                        init_async_state, pack_problem, solve_batched,
                        step_batched)
from repro.obs import (AsyncSolveTrace, FakeClock, Registry, SolveTrace,
                       dispatch_count)
from repro.obs import export as obs_export
from repro.obs import spans as obs_spans

TOL = dict(rtol=1e-9, atol=1e-12)
ROUNDS = 10
KEY = jax.random.PRNGKey(7)
BACKENDS = ("xla", "pallas", "pallas_fused")
CENSOR = dict(censor_tau=2e-2, censor_decay=0.9)

TOPOLOGIES = {
    "circulant": (circulant(6, (1, 2)), [8, 10, 12, 8, 10, 12]),
    "star": (star(5), [6, 8, 10, 12, 14]),
    "er": (erdos_renyi(6, 0.5, seed=2), [9, 11, 9, 11, 9, 11]),
    "j1": (Topology(adjacency=np.zeros((1, 1), dtype=bool)), [10]),
}

_CACHE: dict = {}


@pytest.fixture(scope="module", autouse=True)
def _release_executables():
    """Drop the global executable caches once this module finishes.

    The trace-exactness matrix below compiles the whole solver surface
    — topologies x backends x sync/async x {plain, trace, stats} — on
    top of everything the preceding tier-1 modules already cached.  In
    one long pytest process that pushes the CPU JIT past its code
    budget and LLVM segfaults compiling an unrelated program a few
    files later (tests/test_stream.py).  Clearing here keeps the full
    run inside the budget; later modules recompile what they need.
    """
    yield
    _CACHE.clear()
    jax.clear_caches()


def _packed(name):
    if name not in _CACHE:
        topo, dims = TOPOLOGIES[name]
        j = topo.num_nodes
        ds, train, _ = cached_split("air_quality", j, subsample=300, seed=0)
        fmaps = cached_fmaps("air_quality", j, tuple(dims),
                             subsample=300, seed=0)
        n = sum(t.num_samples for t in train)
        _CACHE[name] = pack_problem(DeKRRSolver(
            topo, fmaps, train, DeKRRConfig(lam=1e-6, c_nei=0.02 * n)))
    return _CACHE[name]


def _per_bcast_bytes(packed):
    return (packed.max_features * packed.num_outputs
            * np.dtype(packed.d.dtype).itemsize)


def _sync_recompute(packed, rounds):
    """Per-round reference series from the public single-round step."""
    theta, res = jnp.zeros_like(packed.d), []
    for _ in range(rounds):
        new = step_batched(packed, theta)
        res.append(float(jnp.max(jnp.abs(new - theta))))
        theta = new
    return theta, np.asarray(res)


def _async_recompute(packed, rounds, key, config):
    """Per-round reference: drive `async_step_batched` one round at a
    time from the same precomputed schedule the solver consumes."""
    masks = activation_masks(key, rounds, packed.num_nodes,
                             prob=config.prob, gossip=config.gossip)
    thresholds = censor_schedule(config.censor_tau, config.censor_decay,
                                 rounds, dtype=packed.d.dtype)
    state = init_async_state(packed)
    res, active, bcasts, delivs = [], [], [], []
    for r in range(rounds):
        new, info = async_step_batched(
            packed, state, masks[r], thresholds[r], gossip=config.gossip,
            censored=config.censored)
        res.append(float(jnp.max(jnp.abs(new.theta - state.theta))))
        active.append(int(jnp.sum(masks[r] != 0)))
        bcasts.append(int(jnp.sum(info.bcast)))
        delivs.append(int(jnp.sum(info.received)))
        state = new
    return (state.theta, np.asarray(res), np.asarray(active),
            np.asarray(bcasts), np.asarray(delivs))


# --------------------------------------------------------------------------
# Synchronous traces
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_sync_trace_matches_recompute(name, backend):
    packed = _packed(name)
    theta, trace = solve_batched(packed, ROUNDS, backend=backend,
                                 return_trace=True)
    assert isinstance(trace, SolveTrace)
    want_theta, want_res = _sync_recompute(packed, ROUNDS)
    assert trace.residuals.shape == (ROUNDS,)
    np.testing.assert_allclose(np.asarray(theta), np.asarray(want_theta),
                               **TOL)
    np.testing.assert_allclose(np.asarray(trace.residuals), want_res,
                               **TOL)


@pytest.mark.parametrize("chunk", [1, 7, 64])
def test_sync_trace_chunk_invariance(chunk):
    packed = _packed("circulant")
    base = solve_batched(packed, ROUNDS, backend="pallas_fused",
                         return_trace=True)[1]
    got = solve_batched(packed, ROUNDS, backend="pallas_fused",
                        chunk_rounds=chunk, return_trace=True)[1]
    # same kernel, chunk boundaries chain the state bit-exactly
    np.testing.assert_array_equal(np.asarray(got.residuals),
                                  np.asarray(base.residuals))
    got_xla = solve_batched(packed, ROUNDS, backend="xla",
                            chunk_rounds=chunk, return_trace=True)[1]
    np.testing.assert_allclose(np.asarray(got_xla.residuals),
                               np.asarray(base.residuals), **TOL)


@pytest.mark.parametrize("chunk", [1, 7, 64])
def test_sync_tol_trace_frozen_rounds(chunk):
    packed = _packed("circulant")
    iters = 200
    full = solve_batched(packed, iters, backend="xla",
                         return_trace=True)[1]
    theta, rounds, trace = solve_batched(
        packed, iters, backend="xla", tol=1e-4, chunk_rounds=chunk,
        return_rounds=True, return_trace=True)
    rd = int(rounds)
    assert 0 < rd < iters, "tol must actually stop the solve early"
    assert trace.residuals.shape == (iters,)
    # every executed round recorded exactly what the tol=0 run recorded;
    # rounds that never ran are exactly 0
    np.testing.assert_allclose(np.asarray(trace.residuals[:rd]),
                               np.asarray(full.residuals[:rd]), **TOL)
    np.testing.assert_array_equal(np.asarray(trace.residuals[rd:]),
                                  np.zeros(iters - rd))


# --------------------------------------------------------------------------
# Asynchronous traces
# --------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("name", sorted(TOPOLOGIES))
def test_async_trace_matches_recompute(name, backend):
    packed = _packed(name)
    config = AsyncGossipConfig(prob=0.5, **CENSOR)
    theta, stats, trace = async_solve_batched(
        packed, ROUNDS, KEY, config=config, backend=backend,
        return_stats=True, return_trace=True)
    assert isinstance(trace, AsyncSolveTrace)
    want = _async_recompute(packed, ROUNDS, KEY, config)
    np.testing.assert_allclose(np.asarray(theta), np.asarray(want[0]),
                               **TOL)
    np.testing.assert_allclose(np.asarray(trace.residuals), want[1], **TOL)
    for got, ref, label in ((trace.active, want[2], "active"),
                            (trace.broadcasts, want[3], "broadcasts"),
                            (trace.deliveries, want[4], "deliveries")):
        np.testing.assert_array_equal(np.asarray(got), ref, err_msg=label)
    np.testing.assert_array_equal(
        np.asarray(trace.bytes),
        np.asarray(trace.broadcasts) * _per_bcast_bytes(packed))
    # summing the series reproduces the cumulative stats — in particular
    # on "pallas_fused", where return_stats used to silently fall back
    # to the per-round path and now reads the kernel's trace blocks
    assert int(stats.broadcasts) == int(np.sum(want[3]))
    assert int(stats.deliveries) == int(np.sum(want[4]))
    assert int(stats.rounds) == ROUNDS


def test_async_fused_trace_chunk_invariance():
    packed = _packed("circulant")
    config = AsyncGossipConfig(prob=0.5, **CENSOR)
    base = async_solve_batched(packed, ROUNDS, KEY, config=config,
                               backend="pallas_fused",
                               return_trace=True)[1]
    for chunk in (1, 7, 64):
        got = async_solve_batched(packed, ROUNDS, KEY, config=config,
                                  backend="pallas_fused",
                                  chunk_rounds=chunk,
                                  return_trace=True)[1]
        for f in AsyncSolveTrace._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, f)), np.asarray(getattr(base, f)),
                err_msg=f"{f} chunk={chunk}")


@pytest.mark.parametrize("chunk", [1, 7, 64])
def test_async_tol_trace_frozen_rounds(chunk):
    packed = _packed("circulant")
    config = AsyncGossipConfig(prob=0.5, **CENSOR)
    iters = 200
    full = async_solve_batched(packed, iters, KEY, config=config,
                               return_trace=True)[1]
    theta, rounds, trace = async_solve_batched(
        packed, iters, KEY, config=config, tol=1e-4, chunk_rounds=chunk,
        return_rounds=True, return_trace=True)
    rd = int(rounds)
    assert 0 < rd < iters, "tol must actually stop the solve early"
    for f in AsyncSolveTrace._fields:
        got, ref = np.asarray(getattr(trace, f)), getattr(full, f)
        assert got.shape == (iters,), f
        kw = TOL if f == "residuals" else dict(rtol=0, atol=0)
        np.testing.assert_allclose(got[:rd], np.asarray(ref)[:rd],
                                   err_msg=f, **kw)
        np.testing.assert_array_equal(got[rd:], np.zeros(iters - rd),
                                      err_msg=f)


def test_async_degenerate_matches_sync_trace():
    """prob=1 bernoulli uncensored: the async residual series IS the
    synchronous one (same program shape ⇒ bit-for-bit on xla)."""
    packed = _packed("circulant")
    sync = solve_batched(packed, ROUNDS, return_trace=True)[1]
    got = async_solve_batched(packed, ROUNDS, KEY,
                              config=AsyncGossipConfig(),
                              return_trace=True)[1]
    np.testing.assert_array_equal(np.asarray(got.residuals),
                                  np.asarray(sync.residuals))
    j, k = packed.nbr_mask.shape
    live = int(jnp.sum(packed.nbr_mask != 0))
    np.testing.assert_array_equal(np.asarray(got.active), np.full(ROUNDS, j))
    np.testing.assert_array_equal(np.asarray(got.broadcasts),
                                  np.full(ROUNDS, j))
    np.testing.assert_array_equal(np.asarray(got.deliveries),
                                  np.full(ROUNDS, live))


def test_censored_fraction():
    packed = _packed("circulant")
    trace = async_solve_batched(
        packed, ROUNDS, KEY, config=AsyncGossipConfig(prob=0.5, **CENSOR),
        return_trace=True)[1]
    active = np.asarray(trace.active)
    censored = active - np.asarray(trace.broadcasts)
    assert censored.sum() > 0, "censor threshold never fired — vacuous"
    cf = np.asarray(trace.censored_fraction())
    assert ((cf >= 0) & (cf <= 1)).all()
    np.testing.assert_array_equal(cf[active == 0],
                                  np.zeros((active == 0).sum()))
    # list round-trip (what trace_event exports) agrees — the device cf
    # divides in f32 (int32 promotion), the list path in f64
    cf_lists = AsyncSolveTrace(**{
        k: v for k, v in trace.as_lists().items()}).censored_fraction()
    np.testing.assert_allclose(np.asarray(cf_lists), cf, rtol=1e-6)


# --------------------------------------------------------------------------
# Chebyshev traces
# --------------------------------------------------------------------------
def test_chebyshev_trace():
    packed = _packed("circulant")
    iters, mu = 8, 0.9
    base = chebyshev_solve_packed(packed, mu, num_iters=iters,
                                  return_trace=True)
    theta, trace = base
    assert trace.residuals.shape == (iters,)
    # per-round recomputation: Δ_k = θ_{k+1} − θ_k from prefix solves
    prefixes = [np.asarray(chebyshev_solve_packed(packed, mu,
                                                  num_iters=k))
                for k in range(iters + 1)]
    want = np.asarray([np.max(np.abs(prefixes[k + 1] - prefixes[k]))
                       for k in range(iters)])
    np.testing.assert_allclose(np.asarray(trace.residuals), want, **TOL)
    for backend in ("pallas", "pallas_fused"):
        got = chebyshev_solve_packed(packed, mu, num_iters=iters,
                                     backend=backend, return_trace=True)[1]
        np.testing.assert_allclose(np.asarray(got.residuals),
                                   np.asarray(trace.residuals),
                                   err_msg=backend, **TOL)
    fused = chebyshev_solve_packed(packed, mu, num_iters=iters,
                                   backend="pallas_fused",
                                   return_trace=True)[1]
    for chunk in (1, 3, 64):
        got = chebyshev_solve_packed(packed, mu, num_iters=iters,
                                     backend="pallas_fused",
                                     chunk_rounds=chunk,
                                     return_trace=True)[1]
        np.testing.assert_array_equal(np.asarray(got.residuals),
                                      np.asarray(fused.residuals),
                                      err_msg=f"chunk={chunk}")


# --------------------------------------------------------------------------
# Zero-cost proofs (tracing only — nothing executes)
# --------------------------------------------------------------------------
def test_trace_adds_zero_dispatches():
    """J002: return_trace/return_stats pin the SAME pallas_call counts as
    the plain solve on every backend."""
    packed = _packed("j1")
    pins = {"xla": 0, "pallas": ROUNDS, "pallas_fused": 1}
    for b, pin in pins.items():
        for kw in ({}, {"return_trace": True}):
            n, exact = dispatch_count(solve_batched, packed,
                                      num_iters=ROUNDS, backend=b, **kw)
            assert (n, exact) == (pin, True), (b, kw)
            n, exact = dispatch_count(
                lambda pk, k, b=b, kw=kw: async_solve_batched(
                    pk, ROUNDS, k, backend=b,
                    config=AsyncGossipConfig(prob=0.5, **CENSOR),
                    return_stats=True, **kw),
                packed, KEY)
            assert (n, exact) == (pin, True), (b, kw)
        n, exact = dispatch_count(
            lambda pk, b=b: chebyshev_solve_packed(
                pk, 0.9, num_iters=ROUNDS, backend=b, return_trace=True),
            packed)
        assert (n, exact) == (pin, True), b


def test_trace_no_host_callbacks_and_shapes():
    """J001 on every traced program, plus eval_shape of the trace pytree
    — both pure tracing."""
    from repro.analysis.jaxpr_lint import check_no_callbacks_in_loops

    packed = _packed("circulant")
    config = AsyncGossipConfig(prob=0.5, **CENSOR)
    for b in BACKENDS:
        for tol in (0.0, 1e-4):
            closed = jax.make_jaxpr(
                lambda pk, b=b, tol=tol: solve_batched(
                    pk, ROUNDS, backend=b, tol=tol,
                    return_trace=True))(packed)
            assert check_no_callbacks_in_loops(closed, f"sync:{b}") == []
            closed = jax.make_jaxpr(
                lambda pk, k, b=b, tol=tol: async_solve_batched(
                    pk, ROUNDS, k, config=config, backend=b, tol=tol,
                    return_trace=True))(packed, KEY)
            assert check_no_callbacks_in_loops(closed, f"async:{b}") == []
    shapes = jax.eval_shape(
        lambda pk, k: async_solve_batched(pk, ROUNDS, k, config=config,
                                          return_trace=True)[1],
        packed, KEY)
    assert shapes.residuals.shape == (ROUNDS,)
    for f in ("active", "broadcasts", "deliveries", "bytes"):
        assert getattr(shapes, f).shape == (ROUNDS,)
        assert getattr(shapes, f).dtype == jnp.int32


# --------------------------------------------------------------------------
# SPMD traces (subprocess: forced 4-device CPU platform)
# --------------------------------------------------------------------------
OBS_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import (AsyncGossipConfig, DeKRRConfig, DeKRRSolver,
                            circulant, select_features)
    from repro.data.synthetic import (make_dataset, partition,
                                      train_test_split_nodes)
    from repro.dist import (async_solve_batched, make_async_spmd_solver,
                            make_spmd_solver, pack_problem, solve_batched)

    ROUNDS = 10
    KEY = jax.random.PRNGKey(7)
    TOL = dict(rtol=1e-9, atol=1e-12)
    ds = make_dataset("air_quality", subsample=300, seed=0)
    dims = [8, 10, 8, 10]
    train, _ = train_test_split_nodes(partition(ds, 4, mode="noniid_y"))
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    fmaps = [select_features(keys[j], ds.dim, dims[j], 1.0, train[j].x,
                             train[j].y, method="energy",
                             candidate_ratio=5) for j in range(4)]
    n = sum(t.num_samples for t in train)
    packed = pack_problem(DeKRRSolver(circulant(4, (1,)), fmaps, train,
                                      DeKRRConfig(lam=1e-6,
                                                  c_nei=0.02 * n)))
    mesh = Mesh(np.array(jax.devices()[:4]), ("nodes",))
    cfg = AsyncGossipConfig(prob=0.5, censor_tau=2e-2, censor_decay=0.9)
    for mode in ("ppermute", "allgather"):
        for tol in (0.0, 1e-4):
            got = make_spmd_solver(mesh, "nodes", mode)(
                packed, ROUNDS, tol=tol, return_rounds=True,
                return_trace=True)
            want = solve_batched(packed, ROUNDS, tol=tol,
                                 return_rounds=True, return_trace=True)
            assert int(got[1]) == int(want[1]), (mode, tol)
            np.testing.assert_allclose(np.asarray(got[2].residuals),
                                       np.asarray(want[2].residuals),
                                       err_msg=f"sync {mode} {tol}", **TOL)
            g = make_async_spmd_solver(mesh, "nodes", mode)(
                packed, ROUNDS, KEY, cfg, tol=tol, return_trace=True)
            w = async_solve_batched(packed, ROUNDS, KEY, config=cfg,
                                    tol=tol, return_trace=True)
            np.testing.assert_allclose(np.asarray(g[1].residuals),
                                       np.asarray(w[1].residuals),
                                       err_msg=f"async {mode} {tol}",
                                       **TOL)
            for f in ("active", "broadcasts", "deliveries", "bytes"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(g[1], f)),
                    np.asarray(getattr(w[1], f)),
                    err_msg=f"async {mode} {tol} {f}")
    print("OBS-SPMD-TRACE-OK")
""")


def test_spmd_trace_subprocess():
    """SPMD traces (sync + async, both exchange modes, tol ∈ {0, >0})
    match the batched traces — in a subprocess so the forced 4-device
    platform does not leak into this session."""
    proc = subprocess.run(
        [sys.executable, "-c", OBS_SPMD_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=subprocess_env(), cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OBS-SPMD-TRACE-OK" in proc.stdout


def test_spmd_trace_multidevice_smoke():
    """In-process SPMD trace smoke for CI's forced-4-device jobs;
    skipped in the normal 1-device tier-1 session."""
    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices (CI kernels job forces 4)")
    from jax.sharding import Mesh

    from repro.dist import make_spmd_solver

    topo = circulant(4, (1,))
    dims = [8, 10, 8, 10]
    ds, train, _ = cached_split("air_quality", 4, subsample=300, seed=0)
    fmaps = cached_fmaps("air_quality", 4, tuple(dims), subsample=300,
                         seed=0)
    n = sum(t.num_samples for t in train)
    packed = pack_problem(DeKRRSolver(topo, fmaps, train,
                                      DeKRRConfig(lam=1e-6,
                                                  c_nei=0.02 * n)))
    mesh = Mesh(np.array(jax.devices()[:4]), ("nodes",))
    got = make_spmd_solver(mesh, "nodes", "ppermute")(
        packed, ROUNDS, return_trace=True)[1]
    want = solve_batched(packed, ROUNDS, return_trace=True)[1]
    np.testing.assert_allclose(np.asarray(got.residuals),
                               np.asarray(want.residuals), **TOL)


# --------------------------------------------------------------------------
# Host-side metrics / spans
# --------------------------------------------------------------------------
def test_registry_metrics_with_fake_clock():
    clock = FakeClock()
    reg = Registry(clock=clock)
    reg.counter("c", help="a counter").inc()
    reg.counter("c").inc(2.5)
    assert reg.counter("c").value == 3.5
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    reg.gauge("g").set(4.0)
    reg.gauge("g").add(-1.5)
    assert reg.gauge("g").value == 2.5
    h = reg.histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    with h.time():
        clock.advance(0.5)
    s = h.summary()
    assert s["count"] == 5 and s["max"] == 4.0
    assert s["p50"] == np.percentile([1, 2, 3, 4, 0.5], 50)
    with pytest.raises(TypeError):
        reg.gauge("c")  # name already registered as a Counter
    ev = reg.record_event("trace", label="x")
    assert ev["event"] == "trace" and ev["t"] == clock()


def test_spans_nest_and_noop_without_recorder():
    # library-side span with no recorder installed: pure pass-through
    with obs_spans.span("orphan", x=1):
        pass
    reg = Registry(clock=FakeClock())
    clock = FakeClock()
    with obs_spans.recording(reg, clock=clock) as rec:
        with obs_spans.span("outer", nodes=6):
            clock.advance(1.0)
            with obs_spans.span("inner"):
                clock.advance(0.25)
    assert obs_spans._installed is None, "recorder must uninstall on exit"
    assert [sp.name for sp in rec.spans] == ["inner", "outer"]
    inner, outer = rec.spans
    assert (inner.depth, inner.parent) == (1, "outer")
    assert (outer.depth, outer.parent) == (0, None)
    assert inner.duration == 0.25 and outer.duration == 1.25
    assert outer.attrs == {"nodes": 6}
    assert [sp.name for sp in reg.spans] == ["inner", "outer"]


def test_instrumented_pack_problem_emits_span():
    topo, dims = TOPOLOGIES["j1"]
    ds, train, _ = cached_split("air_quality", 1, subsample=300, seed=0)
    fmaps = cached_fmaps("air_quality", 1, tuple(dims), subsample=300,
                         seed=0)
    solver = DeKRRSolver(topo, fmaps, train, DeKRRConfig(lam=1e-6))
    reg = Registry()
    with obs_spans.recording(reg):
        pack_problem(solver)
    names = [sp.name for sp in reg.spans]
    assert "pack_problem" in names
    sp = reg.spans[names.index("pack_problem")]
    assert sp.attrs["nodes"] == 1


def _tiny_ring_solver():
    """A 4-node ring at a tiny size, cos_bias DDRF maps (Pallas Gram ok)."""
    ds, train, _ = cached_split("air_quality", 4, subsample=200, seed=0)
    fmaps = cached_fmaps("air_quality", 4, (6, 8, 6, 8), subsample=200,
                         seed=0)
    return ds, train, DeKRRSolver(circulant(4, (1,)), fmaps, train,
                                  DeKRRConfig(lam=1e-6), build_aux=False)


def _timeline_events(trace_dir):
    """[(name, start_ns, end_ns, stats)] of the program's host events in
    the newest profiler trace under `trace_dir`."""
    import glob

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(obs_spans.TIMELINE_PREFIX)]


def test_timeline_spans_nest_on_profiler_trace(tmp_path):
    """A recorder puts pack_problem's spans on the profiler's host plane:
    pack.stage, then pack.gram, both inside pack_problem; and each count
    as a marker inside the span that counted it, summing to the
    recorder's count."""
    _, _, solver = _tiny_ring_solver()
    pack_problem(solver, gram_backend="pallas")         # compile outside
    with jax.profiler.trace(str(tmp_path)):
        with obs_spans.recording() as rec:
            pack_problem(solver, gram_backend="pallas")
    assert [(sp.name, sp.parent) for sp in rec.spans] == [
        ("pack.stage", "pack_problem"), ("pack.gram", "pack_problem"),
        ("pack_problem", None)]
    pre, cpre = obs_spans.TIMELINE_PREFIX, obs_spans.COUNT_PREFIX
    timeline = _timeline_events(tmp_path)
    events = {n[len(pre):]: (s, e, st) for n, s, e, st in timeline
              if not n.startswith(cpre)}
    assert set(events) == {"pack_problem", "pack.stage", "pack.gram"}
    p0, p1, attrs = events["pack_problem"]
    s0, s1, _ = events["pack.stage"]
    g0, g1, _ = events["pack.gram"]
    assert p0 <= s0 < s1 <= g0 < g1 <= p1
    assert attrs == {"nodes": 4, "method": "batched"}
    marks = [(n[len(cpre):], s, st["n"]) for n, s, _, st in timeline
             if n.startswith(cpre)]
    assert {n for n, _, _ in marks} <= {"pack.h2d_bytes", obs_spans.COMPILES}
    h2d = [(s, v) for n, s, v in marks if n == "pack.h2d_bytes"]
    assert len(h2d) == 4        # Gram pass ×2, then the build and finish
    assert all(g0 <= s < g1 for s, _ in h2d[:2])
    assert all(g1 <= s < p1 for s, _ in h2d[2:])
    assert sum(v for _, v in h2d) == rec.counts["pack.h2d_bytes"]


def _imbalanced_solver():
    """Ten nodes of 3…57 samples and 4…12 features on circulant(10,
    (1, 2)): every axis of the packed layout is padded."""
    _, train, fmaps = cached_imbalanced("air_quality", 10, 8)
    return DeKRRSolver(circulant(10, (1, 2)), fmaps, train,
                       DeKRRConfig(lam=1e-6), build_aux=False)


@pytest.mark.parametrize("gram_backend", ["xla", "pallas"])
def test_pack_and_solve_spans_state_the_padded_shape(tmp_path, gram_backend):
    """pack.stage states the layout it padded to (nodes, slots, f_max,
    d_max, n_max, as the staged arrays have them) and solve.batched the
    packed shape each round streams (nodes, slots, d_max), on the
    recorder and on the profiler's timeline."""
    from repro.dist.dekrr_spmd import _stage_packed_inputs

    solver = _imbalanced_solver()
    staged = _stage_packed_inputs(solver, gram_backend=gram_backend)
    packed = pack_problem(solver, gram_backend=gram_backend)
    solve_batched(packed, 5)                            # compile outside
    with jax.profiler.trace(str(tmp_path)):
        with obs_spans.recording() as rec:
            packed = pack_problem(solver, gram_backend=gram_backend)
            solve_batched(packed, 5)
    j_nodes, k_slots = staged["nbr_mask"].shape
    stage = dict(nodes=j_nodes, slots=k_slots,
                 f_max=staged["omega"].shape[1],
                 d_max=staged["feat_mask"].shape[1],
                 n_max=staged["x"].shape[2])
    assert stage == dict(nodes=10, slots=4, f_max=12, d_max=12, n_max=57)
    solve = dict(nodes=10, slots=4, d_max=packed.max_features)
    assert [(sp.name, sp.attrs) for sp in rec.spans
            if sp.name in ("pack.stage", "solve.batched")] == [
        ("pack.stage", stage), ("solve.batched", solve)]
    pre = obs_spans.TIMELINE_PREFIX
    assert [(n[len(pre):], st) for n, _, _, st in _timeline_events(tmp_path)
            if n in (pre + "pack.stage", pre + "solve.batched")] == [
        ("pack.stage", stage), ("solve.batched", solve)]


def test_padded_shape_not_reckoned_without_recorder(monkeypatch):
    """Outside a recording neither span reckons its shape nor enters an
    annotation: each stays one attribute read."""
    import repro.dist.dekrr_spmd as spmd_mod

    def refuse(*args, **kwargs):
        raise AssertionError("ran outside a recording")

    solver = _imbalanced_solver()
    packed = pack_problem(solver)
    solve_batched(packed, 5)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(spmd_mod, "_padded_layout", refuse)
    monkeypatch.setattr(obs_spans.SpanRecorder, "span", refuse)
    assert not obs_spans.is_recording()
    solve_batched(pack_problem(solver), 5)


def test_counts_and_timeline_do_nothing_without_recorder(monkeypatch):
    """Outside a recording no annotation is entered, no byte sum is
    computed and no listener stays registered; count() is a no-op."""
    import repro.core.ddrf as ddrf_mod
    import repro.dist.dekrr_spmd as spmd_mod
    from repro.core import select_features

    from jax._src import monitoring as jax_monitoring

    def refuse(*args, **kwargs):
        raise AssertionError("ran outside a recording")

    registered = jax_monitoring.get_event_duration_listeners
    listeners = len(registered())
    with obs_spans.recording():
        assert len(registered()) == listeners + 1
    assert len(registered()) == listeners
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(ddrf_mod, "h2d_nbytes", refuse)
    monkeypatch.setattr(spmd_mod, "h2d_nbytes", refuse)
    ds, train, solver = _tiny_ring_solver()
    obs_spans.count("orphan", 3)
    assert not obs_spans.is_recording()
    select_features(KEY, ds.dim, 6, 1.0, np.asarray(train[0].x),
                    np.asarray(train[0].y), candidate_ratio=5)
    pack_problem(solver)
    assert len(registered()) == listeners
    monkeypatch.undo()
    with obs_spans.recording() as rec:
        with obs_spans.span("recorded"):
            obs_spans.count("n", 2)
    assert rec.counts == {obs_spans.COMPILES: 0, "n": 2}
    assert rec.spans[0].name == "recorded"


def test_compiles_count_a_forced_recompile():
    f = jax.jit(lambda a: a * 2.0 + 1.0)
    a3, a5 = np.ones(3, np.float32), np.ones(5, np.float32)
    with obs_spans.recording() as rec:
        assert rec.counts == {obs_spans.COMPILES: 0}
        f(a3)
        seen = [rec.counts[obs_spans.COMPILES]]
        f(a3)
        seen.append(rec.counts[obs_spans.COMPILES])
        f(a5)                                   # new shape: recompiles
        seen.append(rec.counts[obs_spans.COMPILES])
    assert seen == [1, 1, 2]
    f(a3.reshape(1, 3))                         # after the recording
    assert rec.counts[obs_spans.COMPILES] == 2


@pytest.mark.parametrize("gram_backend", ["xla", "pallas"])
def test_pack_h2d_bytes_equal_staged_nbytes(gram_backend):
    """pack.h2d_bytes is every numpy byte the batched pack uploads: the
    Gram pass's inputs (Pallas only), the staged builder inputs, and the
    finished problem's masks and slot table; ddrf.h2d_bytes is x and y
    when they arrive as numpy."""
    from repro.core import select_features
    from repro.dist.dekrr_spmd import _stage_packed_inputs

    ds, train, solver = _tiny_ring_solver()
    staged = _stage_packed_inputs(solver, gram_backend=gram_backend)
    dims, nbr_idx, _ = staged["_meta"]
    arrays = {k: v for k, v in staged.items() if isinstance(v, np.ndarray)}
    want = sum(a.nbytes for a in arrays.values())
    want += (arrays["feat_mask"].nbytes + nbr_idx.nbytes
             + arrays["nbr_mask"].nbytes)
    if gram_backend == "pallas":
        k_slots = arrays["nbr_mask"].shape[1]
        want += sum(arrays[k].nbytes for k in
                    ("omega", "bias", "x", "y", "col_mask", "x_n",
                     "col_mask_n"))
        want += k_slots * (arrays["omega"].nbytes + arrays["bias"].nbytes)
        assert {"gram_raw", "zy_raw", "gram_cross_raw"} <= set(arrays)
    with obs_spans.recording() as rec:
        pack_problem(solver, gram_backend=gram_backend)
    assert rec.counts["pack.h2d_bytes"] == want

    x, y = np.asarray(train[0].x), np.asarray(train[0].y)
    with obs_spans.recording() as rec:
        select_features(KEY, ds.dim, 6, 1.0, x, y, candidate_ratio=5)
        select_features(KEY, ds.dim, 6, 1.0, jnp.asarray(x),
                        jnp.asarray(y), candidate_ratio=5)
    assert rec.counts["ddrf.h2d_bytes"] == x.nbytes + y.nbytes
    sp = next(s for s in rec.spans if s.name == "ddrf.select")
    assert sp.attrs == {"d0": 30, "D": 6, "N": x.shape[1]}


@pytest.mark.parametrize("method", ["energy", "leverage"])
def test_ddrf_select_span_and_h2d_bytes_every_call(tmp_path, method):
    """Each select_features call, compiled program or not, still gives one
    ddrf.select span with d0, D and N, on the recorder and on the
    profiler's timeline, and a ddrf.h2d_bytes count of its numpy x and y
    inside that span: what the benchmark's DDRF readers look for."""
    from repro.core import select_features

    ds, train, _ = _tiny_ring_solver()
    calls = [(np.asarray(train[j].x), np.asarray(train[j].y), dims)
             for j, dims in ((0, 6), (1, 6), (2, 4))]
    for x, y, dims in calls:                            # compile outside
        select_features(KEY, ds.dim, dims, 1.0, x, y, method=method,
                        candidate_ratio=5)
    with jax.profiler.trace(str(tmp_path)):
        with obs_spans.recording() as rec:
            for j, (x, y, dims) in enumerate(calls):
                select_features(jax.random.fold_in(KEY, j), ds.dim, dims,
                                0.5 + j, x, y, method=method,
                                candidate_ratio=5)
    want = [{"d0": 5 * dims, "D": dims, "N": x.shape[1]}
            for x, _, dims in calls]
    assert [sp.attrs for sp in rec.spans if sp.name == "ddrf.select"] == want
    assert rec.counts["ddrf.h2d_bytes"] == sum(x.nbytes + y.nbytes
                                               for x, y, _ in calls)
    pre, cpre = obs_spans.TIMELINE_PREFIX, obs_spans.COUNT_PREFIX
    timeline = _timeline_events(tmp_path)
    spans = sorted((s, e, st) for n, s, e, st in timeline
                   if n == pre + "ddrf.select")
    assert [st for _, _, st in spans] == want
    marks = sorted((s, st["n"]) for n, s, _, st in timeline
                   if n == cpre + "ddrf.h2d_bytes")
    assert [v for _, v in marks] == [x.nbytes + y.nbytes
                                     for x, y, _ in calls]
    assert all(s0 <= m < s1 for (m, _), (s0, s1, _) in zip(marks, spans))


def test_h2d_nbytes_counts_at_the_device_dtype():
    """With x64 off a 64-bit numpy array is copied at 4 bytes an element;
    device arrays copy nothing."""
    a64, i64, f32 = np.ones(3), np.arange(5), np.ones(2, np.float32)
    with jax.enable_x64(False):
        assert obs_spans.h2d_nbytes(a64, i64, f32, jnp.ones(7)) == 12 + 20 + 8
    with jax.enable_x64(True):
        assert obs_spans.h2d_nbytes(a64, i64, f32) == 24 + 40 + 8


def test_latency_recorder_lives_in_obs():
    from repro.obs.metrics import LatencyRecorder, LatencyReport
    from repro.serve import admission

    assert admission.LatencyRecorder is LatencyRecorder
    assert admission.LatencyReport is LatencyReport
    clock = FakeClock()
    rec = LatencyRecorder(clock=clock)
    assert rec.report() == LatencyReport.empty()
    rec.record(0.0, 1.0)
    rec.record(1.0, 1.5)
    with pytest.raises(ValueError):
        rec.record(2.0, 1.0)
    rep = rec.report()
    assert rep.count == 2 and rep.max == 1.0
    assert rep.qps == 2 / 1.5


# --------------------------------------------------------------------------
# Exporters + report CLI
# --------------------------------------------------------------------------
def _loaded_registry():
    reg = Registry(clock=FakeClock())
    reg.counter("bench.suites_run").inc(2)
    reg.gauge("queue depth").set(3)
    reg.histogram("wave_s").observe(0.25)
    trace = async_solve_batched(
        _packed("j1"), 4, KEY, config=AsyncGossipConfig(),
        return_trace=True)[1]
    obs_export.trace_event(reg, "j1/xla", trace)
    from repro.obs.metrics import LatencyRecorder

    lat = LatencyRecorder(clock=FakeClock())
    lat.record(0.0, 0.5)
    obs_export.latency_event(reg, "serve", lat.report())
    with obs_spans.recording(reg, clock=FakeClock()):
        with obs_spans.span("stage"):
            pass
    return reg


def test_jsonl_and_prometheus_exports(tmp_path):
    reg = _loaded_registry()
    prov = obs_export.provenance(interpret=True, extra={"fast": True})
    assert prov["interpret"] is True and prov["fast"] is True
    path = obs_export.write_jsonl(reg, str(tmp_path / "run.jsonl"), prov)
    records = [json.loads(ln) for ln in
               open(path).read().splitlines()]
    kinds = {r["kind"] for r in records}
    assert kinds == {"provenance", "counter", "gauge", "histogram",
                     "span", "event"}
    tr = next(r for r in records
              if r["kind"] == "event" and r["event"] == "trace")
    assert tr["label"] == "j1/xla" and len(tr["residuals"]) == 4
    assert all(f in tr for f in ("active", "broadcasts", "deliveries",
                                 "bytes"))


def test_provenance_names_the_device_or_raises(monkeypatch):
    """The device block comes from jax; a failure to read it is raised,
    never stamped as an unknown device."""
    import jax

    prov = obs_export.provenance()
    assert prov["platform"] == jax.devices()[0].platform
    assert prov["device_kind"] == jax.devices()[0].device_kind
    assert prov["device_count"] == len(jax.devices())

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError, match="initialize backend"):
        obs_export.provenance()


def test_stamp_provenance(tmp_path):
    prov = {"git_sha": "abc", "t_wall": 0.0}
    d = tmp_path / "BENCH_dict.json"
    d.write_text(json.dumps({"results": [1, 2]}))
    assert obs_export.stamp_provenance(str(d), prov)
    assert json.loads(d.read_text())["provenance"]["git_sha"] == "abc"
    lst = tmp_path / "BENCH_list.json"
    lst.write_text(json.dumps([{"a": 1}]))
    assert obs_export.stamp_provenance(str(lst), prov)
    payload = json.loads(lst.read_text())
    assert payload["provenance"]["git_sha"] == "abc"
    assert payload["results"] == [{"a": 1}]
    assert not obs_export.stamp_provenance(str(tmp_path / "missing.json"),
                                           prov)
    bad = tmp_path / "BENCH_bad.json"
    bad.write_text("not json")
    assert not obs_export.stamp_provenance(str(bad), prov)


def test_report_cli(tmp_path, capsys):
    from repro.obs.__main__ import main

    reg = _loaded_registry()
    path = obs_export.write_jsonl(
        reg, str(tmp_path / "run.jsonl"),
        obs_export.provenance(interpret=True))
    assert main([path]) == 0
    out = capsys.readouterr().out
    for needle in ("provenance", "convergence", "j1/xla", "stage",
                   "bench.suites_run", "serve"):
        assert needle in out, needle


# --------------------------------------------------------------------------
# R006 — the clock chokepoint lint
# --------------------------------------------------------------------------
def test_r006_clock_lint():
    import os

    from repro.analysis.conventions import lint_file

    src = ("import time\n"
           "t0 = time.perf_counter()\n"
           "w = time.time()\n"
           "time.sleep(0.1)\n"
           "ok = time.time()  # analysis: ignore[R006]\n")
    found = lint_file(os.path.join(REPO_ROOT, "src/repro/train/fake.py"),
                      source=src, repo_root=REPO_ROOT)
    assert [f.rule for f in found] == ["R006", "R006"]
    assert "perf_clock" in found[0].message
    assert "wall_clock" in found[1].message
    # repro/obs/ is the sanctioned home of the raw clocks
    assert lint_file(os.path.join(REPO_ROOT, "src/repro/obs/fake.py"),
                     source=src, repo_root=REPO_ROOT) == []
    # outside src/repro/ (tests, benchmarks) the rule does not apply
    assert lint_file(os.path.join(REPO_ROOT, "benchmarks/fake.py"),
                     source=src, repo_root=REPO_ROOT) == []
