"""Cross-backend conformance suite for the async gossip DeKRR runtime.

The async runtime is the first workload where the three execution layers
(ragged reference, packed batched, SPMD nodes-on-devices) can disagree
*silently*: a mask sampled differently, a buffer refreshed on the wrong
round, or a censor decision flipped produces a perfectly plausible — and
wrong — trajectory. This suite pins, under x64 at rtol 1e-9:

  ragged reference (`repro.core.async_gossip_solve`)
    == packed XLA    (`async_solve_batched(backend="xla")`)
    == packed Pallas (`backend="pallas"`, interpret mode on CPU)
    == SPMD subprocess (`make_async_spmd_solver`, forced CPU devices)

swept over {circulant, star, Erdős–Rényi, complete, J=1} ×
{p ∈ 0.25, 0.5, 1.0} × {censored, uncensored}, with the p = 1.0
uncensored column additionally pinned BIT-FOR-BIT against the synchronous
`solve_batched` of the same backend, plus the chunk-size seed-stability
regression for the tol early stop (the chunk-boundary bug class PR 3
fixed for the sync path).
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO_ROOT, cached_fmaps, cached_split, subprocess_env
from repro.core import (AsyncGossipConfig, DeKRRConfig, DeKRRSolver,
                        Topology, async_gossip_solve, circulant, complete,
                        edge_list, edges_from_slot_table, erdos_renyi, star)
from repro.dist import async_solve_batched, pack_problem, solve_batched

TOL = dict(rtol=1e-9, atol=1e-12)
ROUNDS = 15
KEY = jax.random.PRNGKey(7)
# Decaying COKE threshold sized to the test problems' broadcast deltas
# (~1e-2): large enough to censor real broadcasts within ROUNDS (asserted
# below, so the censored column can never go vacuously green), small
# enough that trajectories stay informative.
CENSOR = dict(censor_tau=2e-2, censor_decay=0.9)


def _single_node_topology():
    return Topology(adjacency=np.zeros((1, 1), dtype=bool))


# Same graph sweep as the kernel parity suites: both slot layouts
# (circulant ppermute order, generic padded adjacency) and every degree
# extreme, now under randomized activation.
TOPOLOGIES = {
    "circulant": (circulant(6, (1, 2)), [8, 10, 12, 8, 10, 12]),
    "star": (star(5), [6, 8, 10, 12, 14]),
    "er": (erdos_renyi(6, 0.5, seed=2), [9, 11, 9, 11, 9, 11]),
    "complete": (complete(4), [7, 9, 11, 9]),
    "j1": (_single_node_topology(), [10]),
}

_CACHE: dict = {}


def _problem(name):
    """(solver, packed, dims) for a topology — cached across the matrix
    (parity is exact algebra; every cell reuses the same auxiliaries)."""
    if name not in _CACHE:
        topo, dims = TOPOLOGIES[name]
        j = topo.num_nodes
        ds, train, _ = cached_split("air_quality", j, subsample=300, seed=0)
        fmaps = cached_fmaps("air_quality", j, tuple(dims),
                             subsample=300, seed=0)
        n = sum(t.num_samples for t in train)
        solver = DeKRRSolver(topo, fmaps, train,
                             DeKRRConfig(lam=1e-6, c_nei=0.02 * n))
        _CACHE[name] = (solver, pack_problem(solver), dims)
    return _CACHE[name]


# --------------------------------------------------------------------------
# The conformance matrix: ragged reference vs packed XLA vs packed Pallas
# --------------------------------------------------------------------------
@pytest.mark.parametrize("topo_name", list(TOPOLOGIES))
@pytest.mark.parametrize("prob", [0.25, 0.5, 1.0])
def test_async_conformance_matrix(topo_name, prob):
    """Every (topology, p, censoring) cell: the ragged reference, the
    packed XLA path and the packed Pallas (interpret) path agree at
    rtol 1e-9 under x64 — identical masks, identical censor decisions,
    identical wire traffic, near-identical θ."""
    solver, packed, dims = _problem(topo_name)
    for censored in (False, True):
        config = AsyncGossipConfig(
            prob=prob, **(CENSOR if censored else {}))
        ref = async_gossip_solve(solver, KEY, ROUNDS, config)
        th_xla, stats = async_solve_batched(
            packed, ROUNDS, KEY, config=config, return_stats=True)
        th_pal = async_solve_batched(
            packed, ROUNDS, KEY, config=config, backend="pallas")
        for j in range(solver.J):
            np.testing.assert_allclose(
                np.asarray(th_xla[j][:dims[j]]), np.asarray(ref.theta[j]),
                err_msg=f"xla vs ragged, censored={censored}", **TOL)
            # padding must stay identically zero through pass-throughs too
            assert not np.any(np.asarray(th_xla[j][dims[j]:]))
        np.testing.assert_allclose(
            np.asarray(th_pal), np.asarray(th_xla),
            err_msg=f"pallas vs xla, censored={censored}", **TOL)
        # wire accounting must agree exactly (discrete decisions)
        assert int(stats.broadcasts) == ref.broadcasts
        assert int(stats.deliveries) == ref.deliveries
        assert int(stats.rounds) == ref.rounds == ROUNDS


def test_censoring_actually_suppresses_broadcasts():
    """Guard against a vacuously green censored column: at the matrix's
    threshold schedule, censoring must drop the broadcast count."""
    _, packed, _ = _problem("circulant")
    _, on = async_solve_batched(
        packed, ROUNDS, KEY, config=AsyncGossipConfig(**CENSOR),
        return_stats=True)
    _, off = async_solve_batched(
        packed, ROUNDS, KEY, config=AsyncGossipConfig(), return_stats=True)
    assert int(on.broadcasts) < int(off.broadcasts)
    assert int(on.deliveries) < int(off.deliveries)


@pytest.mark.parametrize("topo_name", ["circulant", "star"])
@pytest.mark.parametrize("censored", [False, True])
def test_async_conformance_edge_gossip(topo_name, censored):
    """Pairwise edge gossip (one uniform edge per round, delivery along
    that edge only) — the mode where per-edge staleness buffers genuinely
    diverge from the senders' last-broadcast vectors."""
    solver, packed, dims = _problem(topo_name)
    config = AsyncGossipConfig(gossip="edge",
                               **(CENSOR if censored else {}))
    ref = async_gossip_solve(solver, KEY, ROUNDS, config)
    th_xla, stats = async_solve_batched(
        packed, ROUNDS, KEY, config=config, return_stats=True)
    th_pal = async_solve_batched(
        packed, ROUNDS, KEY, config=config, backend="pallas")
    for j in range(solver.J):
        np.testing.assert_allclose(
            np.asarray(th_xla[j][:dims[j]]), np.asarray(ref.theta[j]),
            **TOL)
    np.testing.assert_allclose(np.asarray(th_pal), np.asarray(th_xla),
                               **TOL)
    assert int(stats.broadcasts) == ref.broadcasts
    assert int(stats.deliveries) == ref.deliveries
    # edge gossip delivers point-to-point: one delivery per broadcast
    assert ref.deliveries == ref.broadcasts


def test_packed_edge_list_matches_topology_edge_list():
    """`gossip="edge"` draws stay consistent across layers only if the
    packed slot-table edge derivation reproduces the topology's canonical
    edge enumeration bit-for-bit."""
    for name in TOPOLOGIES:
        solver, packed, _ = _problem(name)
        np.testing.assert_array_equal(
            edge_list(solver.topology),
            edges_from_slot_table(np.asarray(packed.nbr_idx),
                                  np.asarray(packed.nbr_mask)),
            err_msg=name)


# --------------------------------------------------------------------------
# p = 1.0, censoring off: bit-for-bit the synchronous solve, per backend
# --------------------------------------------------------------------------
@pytest.mark.parametrize("topo_name", list(TOPOLOGIES))
@pytest.mark.parametrize("backend", ["xla", "pallas", "pallas_fused"])
def test_p1_uncensored_is_bitwise_synchronous(topo_name, backend):
    """The async schedule at full activation IS the Jacobi iteration: the
    async runtime must reproduce `solve_batched` of the SAME backend
    bit-for-bit — any jnp.where, buffer plumbing or mask arithmetic that
    perturbs a single ulp fails this. (backend="pallas_fused" pins the
    fused multi-round async chain against the sync multi-round fused
    kernel — same dot_general sequence, one dispatch each.)
    """
    _, packed, _ = _problem(topo_name)
    sync = solve_batched(packed, ROUNDS, backend=backend)
    asynchronous = async_solve_batched(packed, ROUNDS, KEY,
                                       config=AsyncGossipConfig(),
                                       backend=backend)
    np.testing.assert_array_equal(np.asarray(sync),
                                  np.asarray(asynchronous))


# --------------------------------------------------------------------------
# Fused async chain: bit-parity with the per-round kernel, chunk-invariant
# --------------------------------------------------------------------------
@pytest.mark.parametrize("gossip", ["bernoulli", "edge"])
@pytest.mark.parametrize("censored", [False, True])
def test_fused_async_chain_conformance(gossip, censored):
    """`backend="pallas_fused"` runs the whole schedule (masks, censor
    thresholds, delivery parity) inside one kernel chain. It must be
    BIT-identical to the per-round masked kernel (`backend="pallas"`) —
    both execute the same dot_general sequence at precision=HIGHEST —
    allclose to the XLA path, and invariant to chunk_rounds ∈
    {1, 7, 64} bit for bit."""
    _, packed, dims = _problem("circulant")
    config = AsyncGossipConfig(prob=0.6, gossip=gossip,
                               **(CENSOR if censored else {}))
    th_fused = async_solve_batched(packed, ROUNDS, KEY, config=config,
                                   backend="pallas_fused")
    th_pal = async_solve_batched(packed, ROUNDS, KEY, config=config,
                                 backend="pallas")
    th_xla = async_solve_batched(packed, ROUNDS, KEY, config=config)
    np.testing.assert_array_equal(np.asarray(th_fused), np.asarray(th_pal))
    np.testing.assert_allclose(np.asarray(th_fused), np.asarray(th_xla),
                               **TOL)
    for chunk in (1, 7, 64):
        chunked = async_solve_batched(packed, ROUNDS, KEY, config=config,
                                      backend="pallas_fused",
                                      chunk_rounds=chunk)
        np.testing.assert_array_equal(np.asarray(chunked),
                                      np.asarray(th_fused),
                                      err_msg=f"chunk_rounds={chunk}")


def test_fused_async_chain_splits_long_schedules(monkeypatch):
    """The fused chain prefetches its [R, J] activation table into SMEM
    (1 MiB on a v5e core, rows padded to 128 lanes), so a schedule longer
    than `_max_fused_rounds` runs in several dispatches — bit-identical
    to one, whatever chunk_rounds asked for."""
    from repro.core import activation_masks, censor_schedule
    from repro.dist import async_gossip as ag
    from repro.obs.dispatch import dispatch_count

    _, packed, _ = _problem("circulant")
    config = AsyncGossipConfig(prob=0.6, **CENSOR)
    rounds = 11
    whole = async_solve_batched(packed, rounds, KEY, config=config,
                                backend="pallas_fused")
    monkeypatch.setattr(ag, "_SMEM_TABLE_BYTES", 4 * 128 * 4)
    assert ag._max_fused_rounds(packed.num_nodes) == 4
    masks = activation_masks(KEY, rounds, packed.num_nodes, prob=config.prob)
    thresholds = censor_schedule(config.censor_tau, config.censor_decay,
                                 rounds, dtype=packed.d.dtype)

    def fused(pk):
        return ag._async_solve_fused(
            pk, ag.init_async_state(pk), masks, thresholds,
            gossip=config.gossip, censored=True, chunk_rounds=64)

    assert dispatch_count(fused, packed) == (3, True)     # 4 + 4 + 3
    np.testing.assert_array_equal(np.asarray(fused(packed)),
                                  np.asarray(whole))


def test_fused_async_stats_fall_back_to_per_round():
    """return_stats=True keeps the per-round accounting path even under
    backend="pallas_fused" — its θ and wire counts must match XLA's."""
    _, packed, _ = _problem("circulant")
    config = AsyncGossipConfig(prob=0.6, **CENSOR)
    th_fused, stats_fused = async_solve_batched(
        packed, ROUNDS, KEY, config=config, backend="pallas_fused",
        return_stats=True)
    th_xla, stats_xla = async_solve_batched(
        packed, ROUNDS, KEY, config=config, return_stats=True)
    np.testing.assert_allclose(np.asarray(th_fused), np.asarray(th_xla),
                               **TOL)
    assert int(stats_fused.broadcasts) == int(stats_xla.broadcasts)
    assert int(stats_fused.deliveries) == int(stats_xla.deliveries)
    assert int(stats_fused.rounds) == int(stats_xla.rounds) == ROUNDS


# --------------------------------------------------------------------------
# Seed-stability regression: tol early stop vs chunk_rounds (async path)
# --------------------------------------------------------------------------
def test_async_tol_rounds_identical_across_chunk_sizes():
    """`async_solve_batched(tol=…, return_rounds=True)` evaluates
    convergence after EVERY round and freezes converged solves, so the
    reported rounds-run AND θ must be identical across chunk_rounds ∈
    {1, 7, 64} — the chunk-boundary early-stop bug class PR 3 fixed for
    the sync path must not re-enter through the async scan."""
    _, packed, _ = _problem("circulant")
    config = AsyncGossipConfig(prob=0.5)
    results = {
        chunk: async_solve_batched(packed, 500, KEY, config=config,
                                   tol=1e-8, chunk_rounds=chunk,
                                   return_rounds=True)
        for chunk in (1, 7, 64)
    }
    theta_ref, rounds_ref = results[1]
    assert 0 < int(rounds_ref) < 500, "tol never triggered — bad test"
    for chunk, (theta, rounds) in results.items():
        assert int(rounds) == int(rounds_ref), f"chunk_rounds={chunk}"
        np.testing.assert_array_equal(np.asarray(theta),
                                      np.asarray(theta_ref),
                                      err_msg=f"chunk_rounds={chunk}")


def test_async_tol_ignores_all_silent_rounds():
    """Regression: a round whose Bernoulli draw activates NO nodes has
    Δθ ≡ 0 by construction — the tol stop must not mistake that idle
    round for convergence and return θ = 0 after one round. (At p = 0.25,
    J = 6 an all-silent round occurs with probability (1−p)^J ≈ 18% per
    round, so this key's schedule opens with one.)"""
    from repro.core import activation_masks

    _, packed, _ = _problem("circulant")
    prob = 0.25
    masks = np.asarray(activation_masks(KEY, 3, packed.num_nodes,
                                        prob=prob))
    assert not masks[0].any(), "precondition: round 0 must be all-silent"
    theta, rounds = async_solve_batched(
        packed, 500, KEY, config=AsyncGossipConfig(prob=prob), tol=1e-8,
        return_rounds=True)
    assert int(rounds) > 1, "stopped on the idle round"
    assert np.any(np.asarray(theta)), "converged to the θ0 = 0 iterate"


def test_async_tol_agrees_with_ragged_reference_early_stop():
    """The per-round freeze must stop on the same round as the reference
    solver's break (the converging round is counted in both)."""
    solver, packed, dims = _problem("circulant")
    config = AsyncGossipConfig(prob=0.5)
    ref = async_gossip_solve(solver, KEY, 500, config, tol=1e-8)
    theta, rounds = async_solve_batched(packed, 500, KEY, config=config,
                                        tol=1e-8, return_rounds=True)
    assert int(rounds) == ref.rounds
    for j in range(solver.J):
        np.testing.assert_allclose(np.asarray(theta[j][:dims[j]]),
                                   np.asarray(ref.theta[j]), **TOL)


# --------------------------------------------------------------------------
# Argument validation
# --------------------------------------------------------------------------
def test_async_gossip_rejects_bad_arguments():
    _, packed, _ = _problem("j1")
    with pytest.raises(ValueError, match="prob"):
        AsyncGossipConfig(prob=0.0)
    with pytest.raises(ValueError, match="gossip"):
        AsyncGossipConfig(gossip="ring")
    with pytest.raises(ValueError, match="censor_tau"):
        AsyncGossipConfig(censor_tau=-1.0)
    with pytest.raises(ValueError, match="censor_decay"):
        AsyncGossipConfig(censor_decay=1.5)
    with pytest.raises(ValueError, match="backend"):
        async_solve_batched(packed, 5, KEY, backend="cuda")
    with pytest.raises(ValueError, match="tol"):
        async_solve_batched(packed, 5, KEY, tol=-1e-6)
    with pytest.raises(ValueError, match="chunk_rounds"):
        async_solve_batched(packed, 5, KEY, chunk_rounds=0)
    # edge gossip needs at least one edge; J=1 has none
    with pytest.raises(ValueError, match="edge"):
        async_solve_batched(packed, 5, KEY,
                            config=AsyncGossipConfig(gossip="edge"))


# --------------------------------------------------------------------------
# SPMD conformance (subprocess: forced CPU device counts must not leak)
# --------------------------------------------------------------------------
SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import (AsyncGossipConfig, DeKRRConfig, DeKRRSolver,
                            Topology, circulant, complete, select_features,
                            star)
    from repro.data.synthetic import (make_dataset, partition,
                                      train_test_split_nodes)
    from repro.dist import (async_solve_batched, make_async_spmd_solver,
                            make_spmd_solver, pack_problem)

    ROUNDS = 10
    KEY = jax.random.PRNGKey(7)
    ds = make_dataset("air_quality", subsample=300, seed=0)

    def build(topo, dims):
        j = topo.num_nodes
        train, _ = train_test_split_nodes(partition(ds, j, mode="noniid_y"))
        keys = jax.random.split(jax.random.PRNGKey(0), j)
        fmaps = [select_features(keys[jj], ds.dim, dims[jj], 1.0,
                                 train[jj].x, train[jj].y, method="energy",
                                 candidate_ratio=5) for jj in range(j)]
        n = sum(t.num_samples for t in train)
        return pack_problem(DeKRRSolver(
            topo, fmaps, train, DeKRRConfig(lam=1e-6, c_nei=0.02 * n)))

    single = Topology(adjacency=np.zeros((1, 1), dtype=bool))
    SWEEP = [
        ("circulant", circulant(6, (1, 2)), [8, 10, 12, 8, 10, 12],
         "ppermute"),
        ("star", star(5), [6, 8, 10, 12, 14], "allgather"),
        ("complete", complete(4), [7, 9, 11, 9], "allgather"),
        ("j1", single, [10], "allgather"),
    ]
    CENSOR = dict(censor_tau=2e-2, censor_decay=0.9)

    for name, topo, dims, mode in SWEEP:
        packed = build(topo, dims)
        mesh = Mesh(np.array(jax.devices()[:topo.num_nodes]), ("nodes",))
        for backend in ("xla", "pallas"):
            runner = make_async_spmd_solver(mesh, "nodes", mode,
                                            backend=backend)
            for prob in (0.25, 0.5, 1.0):
                for censored in (False, True):
                    config = AsyncGossipConfig(
                        prob=prob, **(CENSOR if censored else {}))
                    got = runner(packed, ROUNDS, KEY, config)
                    want = async_solve_batched(packed, ROUNDS, KEY,
                                               config=config)
                    np.testing.assert_allclose(
                        np.asarray(got), np.asarray(want),
                        rtol=1e-9, atol=1e-12,
                        err_msg=f"{name} {backend} p={prob} "
                                f"censored={censored}")
            # p=1 uncensored: bit-for-bit the SYNC SPMD solver, same
            # backend and exchange wiring
            sync = make_spmd_solver(mesh, "nodes", mode,
                                    backend=backend)(packed, ROUNDS)
            got = runner(packed, ROUNDS, KEY, AsyncGossipConfig())
            np.testing.assert_array_equal(np.asarray(sync),
                                          np.asarray(got),
                                          err_msg=f"{name} {backend}")
        if name == "circulant":
            # edge gossip: flag exchange rides the collective
            runner = make_async_spmd_solver(mesh, "nodes", mode)
            for censored in (False, True):
                config = AsyncGossipConfig(
                    gossip="edge", **(CENSOR if censored else {}))
                got = runner(packed, ROUNDS, KEY, config)
                want = async_solve_batched(packed, ROUNDS, KEY,
                                           config=config)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want),
                    rtol=1e-9, atol=1e-12,
                    err_msg=f"edge censored={censored}")
    print("SPMD-ASYNC-CONFORMANCE-OK")
""")


def test_spmd_async_conformance_subprocess():
    """The SPMD column of the conformance matrix, in a subprocess so the
    forced 6-device CPU platform does not leak into this session."""
    proc = subprocess.run(
        [sys.executable, "-c", SPMD_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SPMD-ASYNC-CONFORMANCE-OK" in proc.stdout


def test_spmd_async_multidevice_smoke():
    """In-process SPMD async smoke for CI's 4-device kernels job
    (XLA_FLAGS=--xla_force_host_platform_device_count=4); skipped in the
    normal 1-device tier-1 session."""
    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices (CI kernels job forces 4)")
    from jax.sharding import Mesh
    from repro.dist import make_async_spmd_solver

    topo = circulant(4, (1,))
    dims = [8, 10, 8, 10]
    ds, train, _ = cached_split("air_quality", 4, subsample=300, seed=0)
    fmaps = cached_fmaps("air_quality", 4, tuple(dims),
                         subsample=300, seed=0)
    n = sum(t.num_samples for t in train)
    solver = DeKRRSolver(topo, fmaps, train,
                         DeKRRConfig(lam=1e-6, c_nei=0.02 * n))
    packed = pack_problem(solver)
    mesh = Mesh(np.array(jax.devices()[:4]), ("nodes",))
    config = AsyncGossipConfig(prob=0.5, **CENSOR)
    got = make_async_spmd_solver(mesh, "nodes", "ppermute")(
        packed, 10, KEY, config)
    want = async_solve_batched(packed, 10, KEY, config=config)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
