"""Property tests for the packed runtime: pack/unpack round-trips, slot-table
invariants, the §II-C comm cost model (incl. the async expected-bytes
extension), the batched `pack_problem` regression (no per-node tracing;
bit-identical to the per-node replay), the pack downgrade warn/raise
contract, and the async-gossip schedule/staleness invariants."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from conftest import cached_fmaps, cached_split
from repro.core import (DeKRRConfig, DeKRRSolver, activation_mask,
                        activation_masks, circulant, edge_list,
                        erdos_renyi)
from repro.dist import (PackedProblem, async_step_batched,
                        comm_bytes_per_round, init_async_state,
                        pack_problem, pack_theta, unpack_theta)
from repro.dist.dekrr_spmd import (_pack_problem_pernode, _slot_table,
                                   pack_trace_count)


def _synthetic_packed(node_dims, topo, dtype=np.float64) -> PackedProblem:
    """A structurally valid PackedProblem (zero matrices) for a topology."""
    fake = types.SimpleNamespace(
        topology=topo, data=[types.SimpleNamespace(x=np.zeros(1, dtype))])
    nbr_idx, nbr_mask, offsets = _slot_table(fake)
    j, k = nbr_idx.shape
    d_max = max(node_dims)
    theta_mask = (np.arange(d_max)[None, :]
                  < np.asarray(node_dims)[:, None]).astype(dtype)
    return PackedProblem(
        g=jnp.zeros((j, d_max, d_max), dtype),
        d=jnp.zeros((j, d_max), dtype),
        s=jnp.zeros((j, d_max, d_max), dtype),
        p=jnp.zeros((j, k, d_max, d_max), dtype),
        theta_mask=jnp.asarray(theta_mask),
        nbr_idx=jnp.asarray(nbr_idx), nbr_mask=jnp.asarray(nbr_mask),
        offsets=offsets, node_dims=tuple(int(v) for v in node_dims),
    )


# --------------------------------------------------------------------------
# pack_theta / unpack_theta round-trips
# --------------------------------------------------------------------------
@given(j_nodes=st.integers(3, 12), d_lo=st.integers(1, 6),
       d_hi=st.integers(7, 20), seed=st.integers(0, 2**16))
@settings(max_examples=15, deadline=None)
def test_theta_pack_unpack_round_trip(j_nodes, d_lo, d_hi, seed):
    rng = np.random.default_rng(seed)
    dims = rng.integers(d_lo, d_hi + 1, j_nodes)
    packed = _synthetic_packed(dims, circulant(j_nodes, (1,)))

    ragged = [jnp.asarray(rng.normal(size=dj)) for dj in dims]
    theta = pack_theta(packed, ragged)
    assert theta.shape == (j_nodes, max(dims))
    # padded slots are exact zeros == theta_mask complement
    assert not np.any(np.asarray(theta)[np.asarray(packed.theta_mask) == 0])
    back = unpack_theta(packed, theta)
    for a, b in zip(ragged, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the other direction: unpack → pack is the identity on padded θ
    np.testing.assert_array_equal(
        np.asarray(pack_theta(packed, back)), np.asarray(theta))


@given(j_nodes=st.integers(3, 10), seed=st.integers(0, 2**16),
       grow=st.integers(1, 8))
@settings(max_examples=15, deadline=None)
def test_theta_roundtrip_after_dims_refresh_property(j_nodes, seed, grow):
    """A per-node feature refresh changes node_dims (possibly D_max): θ
    from the old packing must re-pad losslessly into the new one when
    dims only grew, and raise a CLEAR error — never silently truncate —
    when a stale θ meets shrunken dims or a re-padded width."""
    rng = np.random.default_rng(seed)
    old_dims = rng.integers(4, 12, j_nodes)
    topo = circulant(j_nodes, (1,))
    old_packed = _synthetic_packed(old_dims, topo)
    ragged = [jnp.asarray(rng.normal(size=dj)) for dj in old_dims]
    theta_old = pack_theta(old_packed, ragged)

    # refresh node 0 with MORE features (D_max may grow): lossless re-pad
    new_dims = old_dims.copy()
    new_dims[0] = old_dims[0] + grow
    new_packed = _synthetic_packed(new_dims, topo)
    carried = list(ragged)
    carried[0] = jnp.zeros(int(new_dims[0]))    # refreshed node: new basis
    repacked = pack_theta(new_packed, carried)
    back = unpack_theta(new_packed, repacked)
    for j in range(1, j_nodes):
        np.testing.assert_array_equal(np.asarray(back[j]),
                                      np.asarray(ragged[j]))
    np.testing.assert_array_equal(
        np.asarray(pack_theta(new_packed, back)), np.asarray(repacked))

    # refresh node 0 with FEWER features: the stale θ is rejected loudly
    shrunk = old_dims.copy()
    shrunk[0] = max(1, old_dims[0] - 1)
    shrunk_packed = _synthetic_packed(shrunk, topo)
    with pytest.raises(ValueError, match="stale"):
        pack_theta(shrunk_packed, ragged)

    # a packed θ of the wrong width never truncates silently
    if max(new_dims) != max(old_dims):
        with pytest.raises(ValueError, match="different packing"):
            unpack_theta(new_packed, theta_old)


# --------------------------------------------------------------------------
# Slot-table invariants
# --------------------------------------------------------------------------
def _slot_table_for(topo):
    fake = types.SimpleNamespace(
        topology=topo, data=[types.SimpleNamespace(x=np.zeros(1))])
    return _slot_table(fake)


@given(j_nodes=st.integers(5, 14), p_edge=st.sampled_from([0.3, 0.5, 0.8]),
       seed=st.integers(0, 2**10))
@settings(max_examples=10, deadline=None)
def test_generic_slot_table_invariants(j_nodes, p_edge, seed):
    """Live slots enumerate each node's true neighbors exactly once; padded
    slots are masked and point at the node itself (harmless gather)."""
    topo = erdos_renyi(j_nodes, p_edge, seed=seed)
    nbr_idx, nbr_mask, offsets = _slot_table_for(topo)
    if offsets is not None:     # an ER draw can happen to be circulant
        return
    for j in range(j_nodes):
        live = nbr_mask[j] != 0
        assert sorted(nbr_idx[j][live].tolist()) == topo.neighbors(j)
        assert np.all(nbr_idx[j][~live] == j)
        # mask is a prefix: live slots first, padding after
        assert not np.any(np.diff(live.astype(int)) > 0)


@given(j_nodes=st.integers(5, 16), use_two=st.sampled_from([False, True]))
@settings(max_examples=10, deadline=None)
def test_circulant_slot_table_is_ppermute_ordered(j_nodes, use_two):
    offsets = (1, 2) if use_two and j_nodes >= 5 else (1,)
    topo = circulant(j_nodes, offsets)
    nbr_idx, nbr_mask, got_offsets = _slot_table_for(topo)
    assert got_offsets == offsets
    assert np.all(nbr_mask == 1)            # circulant layout has no padding
    for j in range(j_nodes):
        want = []
        for s in offsets:
            want.extend([(j + s) % j_nodes, (j - s) % j_nodes])
        assert nbr_idx[j].tolist() == want


def test_packed_masked_slots_carry_zero_p_blocks():
    """The iteration's padding closure relies on masked slots having
    *zero* P blocks, not merely a mask bit."""
    topo = erdos_renyi(6, 0.4, seed=3)
    ds, train, _ = cached_split("air_quality", 6, subsample=400, seed=0)
    fmaps = cached_fmaps("air_quality", 6, (8, 10, 12, 8, 10, 12),
                         subsample=400, seed=0)
    n = sum(t.num_samples for t in train)
    solver = DeKRRSolver(topo, fmaps, train,
                         DeKRRConfig(lam=1e-6, c_nei=0.02 * n))
    packed = pack_problem(solver)
    mask = np.asarray(packed.nbr_mask)
    p = np.asarray(packed.p)
    for j in range(6):
        for k in range(mask.shape[1]):
            if not mask[j, k]:
                assert not np.any(p[j, k])
        # padded θ coordinates: zero rows/cols everywhere
        dj = packed.node_dims[j]
        assert not np.any(np.asarray(packed.g)[j, dj:, :])
        assert not np.any(np.asarray(packed.g)[j, :, dj:])
        assert not np.any(np.asarray(packed.d)[j, dj:])


# --------------------------------------------------------------------------
# §II-C comm cost model: ppermute vs allgather consistency
# --------------------------------------------------------------------------
@given(j_nodes=st.integers(5, 16), use_two=st.sampled_from([False, True]),
       d_max=st.sampled_from([8, 24, 64]))
@settings(max_examples=12, deadline=None)
def test_comm_bytes_consistency_on_circulant_graphs(j_nodes, use_two, d_max):
    offsets = (1, 2) if use_two and j_nodes >= 5 else (1,)
    topo = circulant(j_nodes, offsets)
    dims = [d_max - (j % 3) for j in range(j_nodes)]
    packed = _synthetic_packed(dims, topo)
    itemsize = np.dtype(packed.d.dtype).itemsize

    pp = comm_bytes_per_round(packed, "ppermute")
    ag = comm_bytes_per_round(packed, "allgather")
    # ppermute moves exactly the paper's Σ_j |N_j| padded words…
    assert pp == int(topo.degrees.sum()) * max(dims) * itemsize
    # …allgather moves the full network state minus the own shard…
    assert ag == j_nodes * (j_nodes - 1) * max(dims) * itemsize
    # …and the two models agree on the shared factors: for a circulant
    # graph ppermute/allgather == degree/(J−1) exactly.
    assert pp * (j_nodes - 1) == ag * int(topo.degrees[0])


def test_comm_bytes_equal_on_complete_circulant():
    """On a complete graph both exchanges move the same bytes."""
    from repro.core import complete
    topo = complete(7)
    packed = _synthetic_packed([16] * 7, topo)
    assert (comm_bytes_per_round(packed, "ppermute")
            == comm_bytes_per_round(packed, "allgather"))


# --------------------------------------------------------------------------
# Batched pack_problem regression (the removed per-node Python loop)
# --------------------------------------------------------------------------
def _regression_solver():
    topo = circulant(8, (1, 2))
    dims = (8, 12, 16, 20, 8, 12, 16, 20)
    ds, train, _ = cached_split("air_quality", 8, subsample=400, seed=0)
    fmaps = cached_fmaps("air_quality", 8, dims, subsample=400, seed=0)
    n = sum(t.num_samples for t in train)
    return DeKRRSolver(topo, fmaps, train,
                       DeKRRConfig(lam=1e-6, c_nei=0.02 * n),
                       build_aux=False)


def test_batched_pack_traces_once_and_matches_pernode_loop_bitwise():
    """The batched Eq. 17 build must (a) trace one program per problem
    shape — never once per node, and not again on repeat packing — and
    (b) produce bit-identical PackedProblem contents to the removed
    per-node Python loop (`_pack_problem_pernode`, batch-of-1 replay of
    the same program) on a fixed seed."""
    solver = _regression_solver()

    t0 = pack_trace_count()
    packed = pack_problem(solver)
    traced_first = pack_trace_count() - t0
    assert traced_first <= 1, \
        f"batched pack traced {traced_first}× (per-node tracing?)"

    t1 = pack_trace_count()
    repacked = pack_problem(solver)
    assert pack_trace_count() - t1 == 0, "repeat packing re-traced"

    loop = _pack_problem_pernode(solver)
    for name in ("g", "d", "s", "p", "theta_mask", "nbr_idx", "nbr_mask"):
        batched = np.asarray(getattr(packed, name))
        np.testing.assert_array_equal(
            batched, np.asarray(getattr(repacked, name)),
            err_msg=f"{name}: repeat packing changed bits")
        np.testing.assert_array_equal(
            batched, np.asarray(getattr(loop, name)),
            err_msg=f"{name}: batched != per-node loop")
    assert packed.offsets == loop.offsets
    assert packed.node_dims == loop.node_dims
    # the batched path must never materialize the ragged reference aux
    assert solver._aux is None


def test_batched_pack_matches_reference_aux_pack():
    """Same contents as the legacy `method="aux"` pack (which copies the
    ragged reference build) at solver-parity tolerance — different
    summation orders make bitwise equality impossible across the two
    computations, rtol 1e-9 is the module's contract."""
    solver = _regression_solver()
    batched = pack_problem(solver)
    legacy = pack_problem(solver, method="aux")
    for name in ("d", "s", "p"):
        np.testing.assert_allclose(
            np.asarray(getattr(batched, name)),
            np.asarray(getattr(legacy, name)), rtol=1e-9, atol=1e-15,
            err_msg=name)
    # g is an inverse, so its entrywise agreement degrades with cond(A):
    # looser rtol plus an atol scaled to ||g||_max instead of the 1e-9 used
    # for the directly-computed d/s/p blocks
    g_b, g_l = np.asarray(batched.g), np.asarray(legacy.g)
    np.testing.assert_allclose(g_b, g_l, rtol=1e-6,
                               atol=1e-9 * np.max(np.abs(g_l)))


# --------------------------------------------------------------------------
# pack_problem downgrade contract: warn when the batched build is swapped
# --------------------------------------------------------------------------
def _gram_fn_solver():
    """A solver the batched build cannot honor (custom gram_fn)."""
    topo = circulant(4, (1,))
    ds, train, _ = cached_split("air_quality", 4, subsample=300, seed=0)
    fmaps = cached_fmaps("air_quality", 4, (8, 10, 8, 10),
                         subsample=300, seed=0)
    n = sum(t.num_samples for t in train)
    gram_fn = lambda fm, x: (lambda z: z @ z.T)(fm(x))
    return DeKRRSolver(topo, fmaps, train,
                       DeKRRConfig(lam=1e-6, c_nei=0.02 * n),
                       gram_fn=gram_fn)


def test_pack_problem_warns_on_silent_aux_downgrade():
    """method="batched" with a gram_fn solver must fall back to the aux
    build LOUDLY — the downgrade swaps a vmapped one-trace program for a
    per-node Python loop."""
    solver = _gram_fn_solver()
    with pytest.warns(UserWarning, match="downgraded to method='aux'"):
        packed = pack_problem(solver)
    # the downgrade itself still works and records layout metadata
    assert packed.node_dims == (8, 10, 8, 10)


def test_pack_problem_aux_explicitly_requested_does_not_warn():
    import warnings as _w
    solver = _gram_fn_solver()
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        pack_problem(solver, method="aux")
    assert not [c for c in caught if "downgraded" in str(c.message)], \
        "explicit method='aux' is not a downgrade and must not warn"


# --------------------------------------------------------------------------
# Async gossip: activation-mask determinism from the PRNG key
# --------------------------------------------------------------------------
@given(j_nodes=st.integers(3, 12), seed=st.integers(0, 2**16),
       prob=st.sampled_from([0.25, 0.5, 0.75, 1.0]),
       num_rounds=st.integers(1, 12))
@settings(max_examples=10, deadline=None)
def test_bernoulli_activation_masks_deterministic(j_nodes, seed, prob,
                                                  num_rounds):
    """The precomputed [R, J] schedule must be a pure function of the key
    — recomputation is bit-identical, and row r equals the single-round
    spec `activation_mask(key, r, …)` every layer is defined against."""
    key = jax.random.PRNGKey(seed)
    masks = activation_masks(key, num_rounds, j_nodes, prob=prob)
    again = activation_masks(key, num_rounds, j_nodes, prob=prob)
    np.testing.assert_array_equal(np.asarray(masks), np.asarray(again))
    for r in range(num_rounds):
        np.testing.assert_array_equal(
            np.asarray(masks[r]),
            np.asarray(activation_mask(key, r, j_nodes, prob=prob)),
            err_msg=f"round {r}")
    if prob == 1.0:
        assert np.all(np.asarray(masks))


@given(j_nodes=st.integers(4, 10), seed=st.integers(0, 2**10),
       num_rounds=st.integers(1, 10))
@settings(max_examples=10, deadline=None)
def test_edge_activation_masks_are_single_edges(j_nodes, seed, num_rounds):
    """Every edge-gossip round activates exactly the two endpoints of one
    existing edge, deterministically in the key."""
    topo = erdos_renyi(j_nodes, 0.5, seed=seed % 7)
    edges = edge_list(topo)
    key = jax.random.PRNGKey(seed)
    masks = np.asarray(activation_masks(key, num_rounds, j_nodes,
                                        gossip="edge", edges=edges))
    edge_set = {tuple(e) for e in edges.tolist()}
    for r in range(num_rounds):
        active = np.nonzero(masks[r])[0]
        assert len(active) == 2
        assert tuple(active.tolist()) in edge_set
    np.testing.assert_array_equal(
        masks, np.asarray(activation_masks(key, num_rounds, j_nodes,
                                           gossip="edge", edges=edges)))


# --------------------------------------------------------------------------
# Async gossip: staleness-buffer invariants on the packed runtime
# --------------------------------------------------------------------------
def _random_packed(topo, d_max, seed, dtype=np.float64) -> PackedProblem:
    """A random nonzero PackedProblem (spectra bounded so iterates stay
    finite) on a real slot table — enough structure for wire-traffic
    properties without an Eq. 17 build."""
    fake = types.SimpleNamespace(
        topology=topo, data=[types.SimpleNamespace(x=np.zeros(1, dtype))])
    nbr_idx, nbr_mask, offsets = _slot_table(fake)
    j, k = nbr_idx.shape
    rng = np.random.default_rng(seed)
    scale = 0.3 / d_max
    return PackedProblem(
        g=jnp.asarray(rng.normal(size=(j, d_max, d_max)) * scale),
        d=jnp.asarray(rng.normal(size=(j, d_max))),
        s=jnp.asarray(rng.normal(size=(j, d_max, d_max)) * scale),
        p=jnp.asarray(rng.normal(size=(j, k, d_max, d_max)) * scale
                      * np.asarray(nbr_mask)[:, :, None, None]),
        theta_mask=jnp.ones((j, d_max), dtype),
        nbr_idx=jnp.asarray(nbr_idx), nbr_mask=jnp.asarray(nbr_mask),
        offsets=offsets, node_dims=tuple([d_max] * j),
    )


@given(seed=st.integers(0, 2**10), prob=st.sampled_from([0.25, 0.5, 0.75]),
       censored=st.sampled_from([False, True]))
@settings(max_examples=8, deadline=None)
def test_staleness_buffer_invariant(seed, prob, censored):
    """An inactive (or censored) node's broadcast θ never changes: its
    `sent` vector and every receive buffer fed by it stay bit-identical
    until the node actually broadcasts again — and under bernoulli
    delivery a buffer always equals its sender's last broadcast."""
    topo = erdos_renyi(7, 0.5, seed=seed % 5)
    packed = _random_packed(topo, 8, seed)
    key = jax.random.PRNGKey(seed)
    masks = activation_masks(key, 10, 7, prob=prob)
    nbr_idx = np.asarray(packed.nbr_idx)
    live = np.asarray(packed.nbr_mask) != 0

    state = init_async_state(packed)
    for r in range(10):
        new, info = async_step_batched(
            packed, state, masks[r], threshold=0.05 * 0.9 ** r,
            censored=censored)
        bcast = np.asarray(info.bcast)
        received = np.asarray(info.received)
        # broadcasts are a subset of activations; deliveries of broadcasts
        assert not np.any(bcast & ~np.asarray(masks[r]))
        np.testing.assert_array_equal(received, live & bcast[nbr_idx])
        for j in range(7):
            if not bcast[j]:        # silent node: wire state frozen …
                np.testing.assert_array_equal(
                    np.asarray(new.sent[j]), np.asarray(state.sent[j]),
                    err_msg=f"round {r}: silent node {j} changed sent")
            slots = ~received[j]
            np.testing.assert_array_equal(      # … and so are its buffers
                np.asarray(new.buffers[j][slots]),
                np.asarray(state.buffers[j][slots]),
                err_msg=f"round {r}: undelivered buffer changed")
        # bernoulli delivery: buffer == sender's last broadcast, always
        np.testing.assert_array_equal(
            np.asarray(new.buffers)[live],
            np.asarray(new.sent)[nbr_idx][live],
            err_msg=f"round {r}: buffer diverged from sender's sent")
        state = new


def test_async_state_init_matches_synchronous_view():
    """Round-0 buffers must present θ0 exactly as the synchronous gather
    would — anything else breaks the p = 1 bitwise equivalence."""
    packed = _random_packed(circulant(6, (1, 2)), 8, seed=0)
    theta0 = jnp.asarray(np.random.default_rng(1).normal(size=(6, 8)))
    state = init_async_state(packed, theta0)
    np.testing.assert_array_equal(np.asarray(state.buffers),
                                  np.asarray(theta0)[packed.nbr_idx])
    np.testing.assert_array_equal(np.asarray(state.sent),
                                  np.asarray(theta0))


# --------------------------------------------------------------------------
# Async gossip: expected comm bytes monotone in activation probability
# --------------------------------------------------------------------------
@given(j_nodes=st.integers(5, 14), d_max=st.sampled_from([8, 24, 64]),
       censor=st.sampled_from([0.0, 0.2, 0.6]),
       mode=st.sampled_from(["ppermute", "allgather"]))
@settings(max_examples=12, deadline=None)
def test_expected_comm_bytes_monotone_in_activation_prob(j_nodes, d_max,
                                                         censor, mode):
    """E[bytes/round] is non-decreasing in p, non-increasing in the censor
    fraction, and collapses to the exact synchronous int at the defaults."""
    topo = circulant(j_nodes, (1, 2) if j_nodes >= 5 else (1,))
    packed = _synthetic_packed([d_max] * j_nodes, topo)
    grid = [0.1, 0.25, 0.5, 0.75, 1.0]
    got = [comm_bytes_per_round(packed, mode, activation_prob=p,
                                censor_fraction=censor) for p in grid]
    assert all(a <= b for a, b in zip(got, got[1:])), got
    base = comm_bytes_per_round(packed, mode)
    assert isinstance(base, int)
    assert got[-1] == pytest.approx(base * (1.0 - censor))
    # more censoring, fewer expected bytes (p fixed)
    heavier = comm_bytes_per_round(packed, mode, activation_prob=0.5,
                                   censor_fraction=min(censor + 0.3, 1.0))
    assert heavier <= comm_bytes_per_round(packed, mode,
                                           activation_prob=0.5,
                                           censor_fraction=censor)


def test_expected_comm_bytes_edge_gossip_and_validation():
    packed = _synthetic_packed([16] * 6, circulant(6, (1,)))
    itemsize = np.dtype(np.asarray(packed.d).dtype).itemsize
    # one edge per round: two directed deliveries, independent of p
    assert comm_bytes_per_round(packed, "ppermute", gossip="edge") \
        == 2 * 16 * itemsize
    assert comm_bytes_per_round(
        packed, "ppermute", gossip="edge", activation_prob=0.25,
        censor_fraction=0.5) == pytest.approx(16 * itemsize)
    with pytest.raises(ValueError, match="activation_prob"):
        comm_bytes_per_round(packed, "ppermute", activation_prob=0.0)
    with pytest.raises(ValueError, match="censor_fraction"):
        comm_bytes_per_round(packed, "ppermute", censor_fraction=1.5)
    with pytest.raises(ValueError, match="gossip"):
        comm_bytes_per_round(packed, "ppermute", gossip="pairwise")
