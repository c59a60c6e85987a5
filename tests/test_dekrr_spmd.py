"""Parity tests: packed/batched and SPMD runtimes vs the ragged reference."""
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (REPO_ROOT, cached_fmaps, cached_imbalanced,
                      cached_split, subprocess_env)
from repro.core import (DeKRRConfig, DeKRRSolver, NodeData, circulant,
                        erdos_renyi, star)
from repro.dist import (comm_bytes_per_round, pack_problem, solve_batched,
                        step_batched)


def _problem(topo, D_per_node, sub=400, seed=0):
    """Parity is exact algebra, so a small cached subsample loses nothing."""
    j = topo.num_nodes
    ds, train, _ = cached_split("air_quality", j, subsample=sub, seed=seed)
    fmaps = cached_fmaps("air_quality", j, tuple(D_per_node),
                         subsample=sub, seed=seed)
    n = sum(t.num_samples for t in train)
    return DeKRRSolver(topo, fmaps, train,
                       DeKRRConfig(lam=1e-6, c_nei=0.02 * n))


@pytest.mark.parametrize("topo,dims", [
    (circulant(10, (1, 2)), [8, 12, 16, 20, 24, 8, 12, 16, 20, 24]),
    (circulant(6, (1,)), [10] * 6),
    (star(5), [6, 8, 10, 12, 14]),
    (erdos_renyi(7, 0.5, seed=1), [9, 9, 9, 9, 9, 9, 9]),
])
def test_packed_step_matches_ragged_reference(topo, dims):
    solver = _problem(topo, dims)
    packed = pack_problem(solver)
    state = solver.init_state()
    theta = jnp.zeros_like(packed.d)
    for _ in range(5):
        state = solver.step(state)
        theta = step_batched(packed, theta)
    for j in range(topo.num_nodes):
        np.testing.assert_allclose(
            np.asarray(theta[j][:dims[j]]), np.asarray(state.theta[j]),
            rtol=1e-9, atol=1e-12)
        # padding must stay identically zero
        assert not np.any(np.asarray(theta[j][dims[j]:]))


def test_solve_batched_scan_matches_python_loop():
    topo = circulant(8, (1, 2))
    solver = _problem(topo, [10] * 8)
    packed = pack_problem(solver)
    theta_scan = solve_batched(packed, 30)
    theta = jnp.zeros_like(packed.d)
    for _ in range(30):
        theta = step_batched(packed, theta)
    np.testing.assert_allclose(np.asarray(theta_scan), np.asarray(theta),
                               rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("data", ["full", "first_half"])
def test_imbalanced_pack_and_solve_match_ragged_reference(data):
    """The paper's Fig. 3 layout, where N_j and D_j both differ by node
    (3…57 samples, 4…12 features): the padded pack and the batched solve,
    to a fixed round count and to tol, give the ragged solver's θ, and
    the padded coordinates stay zero. `first_half` builds every node
    from the first half of its samples (the maps drawn on all of them),
    the input of the benchmark's `half_batch` fault."""
    ds, train, fmaps = cached_imbalanced("air_quality", 10, 8)
    if data == "first_half":
        train = [NodeData(x=t.x[:, :t.num_samples // 2],
                          y=t.y[:t.num_samples // 2]) for t in train]
    dims = [fm.num_features for fm in fmaps]
    n = sum(t.num_samples for t in train)
    config = DeKRRConfig(lam=1e-6, c_nei=0.02 * n, tol=1e-6)
    solver = DeKRRSolver(circulant(10, (1, 2)), fmaps, train, config)
    packed = pack_problem(solver)
    assert packed.max_features == max(dims) > min(dims)
    theta = solve_batched(packed, 40)
    ref = solver.solve(num_iters=40)
    theta_tol, rounds = solve_batched(packed, 4000, tol=config.tol,
                                      return_rounds=True)
    ref_tol = solver.solve(num_iters=int(rounds))
    assert 40 < int(rounds) < 4000
    for th, want in ((theta, ref), (theta_tol, ref_tol)):
        for j, dj in enumerate(dims):
            np.testing.assert_allclose(np.asarray(th[j][:dj]),
                                       np.asarray(want.theta[j]),
                                       rtol=1e-9, atol=1e-12)
            assert not np.any(np.asarray(th[j][dj:]))


def test_circulant_packing_slot_order():
    topo = circulant(10, (1, 2))
    solver = _problem(topo, [8] * 10)
    packed = pack_problem(solver)
    assert packed.offsets == (1, 2)
    # slots: [(+1), (−1), (+2), (−2)]
    idx = np.asarray(packed.nbr_idx)
    for j in range(10):
        assert list(idx[j]) == [(j + 1) % 10, (j - 1) % 10,
                                (j + 2) % 10, (j - 2) % 10]


def test_comm_bytes_cost_model():
    topo = circulant(10, (1, 2))
    solver = _problem(topo, [16] * 10)
    packed = pack_problem(solver)
    # Σ_j |N_j| · D_max · 8 bytes = 10·4·16·8
    assert comm_bytes_per_round(packed, "ppermute") == 10 * 4 * 16 * 8


SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={J}"
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import DeKRRConfig, DeKRRSolver, circulant, select_features
    from repro.data.synthetic import make_dataset, partition, train_test_split_nodes
    from repro.dist import make_spmd_solver, pack_problem, solve_batched

    J = {J}
    ds = make_dataset("air_quality", subsample=400, seed=0)
    topo = circulant(J, (1, 2))
    train, _ = train_test_split_nodes(partition(ds, J, mode="noniid_y"))
    keys = jax.random.split(jax.random.PRNGKey(0), J)
    dims = [8 + 2 * (j % 4) for j in range(J)]
    fmaps = [select_features(keys[j], ds.dim, dims[j], 1.0, train[j].x,
                             train[j].y, method="energy", candidate_ratio=5)
             for j in range(J)]
    n = sum(t.num_samples for t in train)
    solver = DeKRRSolver(topo, fmaps, train,
                         DeKRRConfig(lam=1e-6, c_nei=0.02 * n))
    packed = pack_problem(solver)
    want = solve_batched(packed, 40)

    mesh = Mesh(np.array(jax.devices()), ("nodes",))
    for mode in ("ppermute", "allgather"):
        got = make_spmd_solver(mesh, "nodes", mode)(packed, 40)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-9, atol=1e-12)

    # tol early-stop (fused pmax; all devices agree on the stop round) +
    # warm start: must match the batched per-round tol check exactly
    want_t, want_rounds = solve_batched(packed, 600, tol=1e-8,
                                        chunk_rounds=1, return_rounds=True)
    run = make_spmd_solver(mesh, "nodes", "ppermute")
    got_t, got_rounds = run(packed, 600, tol=1e-8, return_rounds=True)
    assert int(got_rounds) == int(want_rounds) < 600, (
        int(got_rounds), int(want_rounds))
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t),
                               rtol=1e-9, atol=1e-12)
    _, rounds2 = run(packed, 600, got_t, tol=1e-8, return_rounds=True)
    assert int(rounds2) <= 1, int(rounds2)
    print("SPMD-PARITY-OK")
""")


@pytest.mark.parametrize("num_nodes", [10])
def test_spmd_parity_on_10_devices(num_nodes):
    """Runs in a subprocess so the forced 10-device CPU platform does not
    leak into this test session (smoke tests must see 1 device)."""
    proc = subprocess.run(
        [sys.executable, "-c", SPMD_SCRIPT.format(J=num_nodes)],
        capture_output=True, text=True, timeout=600,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SPMD-PARITY-OK" in proc.stdout
