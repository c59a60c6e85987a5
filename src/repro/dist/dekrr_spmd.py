"""SPMD nodes-on-devices runtime for the Eq. 19 DeKRR-DDRF iteration.

The reference solver (`repro.core.dekrr.DeKRRSolver`) is deliberately ragged:
a Python loop over nodes, each holding auxiliaries of its own size D_j. That
is the right shape for auditing Algorithm 1 against the paper, and exactly
the wrong shape for hardware. This module is the production counterpart, in
three layers that are pinned to the reference by parity tests
(`tests/test_dekrr_spmd.py`, rtol 1e-9 under x64):

1. **Packing** (`pack_problem`): pad every per-node auxiliary to the network
   maximum D_max and stack over nodes —

     G:  [J, D_max, D_max]      (Eq. 17 inverse, applied)
     d:  [J, D_max]             ((1/N) Z_jj Y_j)
     S:  [J, D_max, D_max]      (2 c̃_self Z_jj Z_jjᵀ)
     P:  [J, K, D_max, D_max]   (neighbor couplings, K slots per node)

   plus `theta_mask` ([J, D_max], 1.0 on live coordinates) and a neighbor
   slot table `nbr_idx`/`nbr_mask` ([J, K]). Because padding is *zero* in the
   matrices (not merely masked), one packed round maps padded inputs to
   padded outputs exactly: row i ≥ D_j of G_j is identically zero, so
   θ_j^{k+1} = G_j(…) has exact 0.0 in every padded coordinate. No masking
   is needed inside the iteration — the algebra is closed over the padding.

   The default `method="batched"` *computes* the Eq. 17 auxiliaries itself,
   directly in the padded [J, D_max, …] layout: one vmapped program
   (featurize → Gram blocks → coupling products → batched inverse) over
   numpy-staged padded inputs, traced once per problem shape regardless of
   J. The Z Zᵀ Gram blocks can optionally be routed through the fused Pallas
   streaming kernel (`repro.kernels.rff_gram`, ``gram_backend="pallas"``,
   the TPU default). `method="aux"` is the legacy path that copies the
   solver's ragged reference auxiliaries (a per-node Python loop) — kept
   for gram_fn-customized solvers and as the reference the batched build is
   regression-tested against.

2. **Batched single-host execution** (`step_batched` / `solve_batched`):
   the Eq. 19 round over all nodes at once, and the full solve over
   rounds. Three backends run the identical arithmetic:

     * ``backend="xla"``  — one `vmap` of `_node_step` over the node axis;
       XLA fuses it into a handful of batched GEMMs (gather of the [J, K,
       D_max] neighbor-θ tensor materialized between them); the solve is
       a `lax.scan` of that round.
     * ``backend="pallas"`` — the fused round kernel
       (`repro.kernels.dekrr_step`): grid over nodes, per step the [D_max,
       D_max] G/S/P blocks stream HBM→VMEM while the θ table stays
       VMEM-resident; the neighbor gather runs inside the kernel via the
       scalar-prefetched slot table. The solve is still a `lax.scan`, one
       kernel dispatch per round. Interpret-mode on CPU, compiled on
       TPU; pinned to the XLA path and the ragged reference at rtol 1e-9
       under x64 by `tests/test_kernels_dekrr_step.py`.
     * ``backend="pallas_fused"`` — the multi-round solve kernel
       (`repro.kernels.dekrr_solve`): the whole scan moves INSIDE one
       pallas_call with grid (rounds, nodes); two VMEM θ tables alternate
       by round parity so θ never round-trips HBM between rounds and the
       per-round dispatch overhead (the dominant cost at the paper's
       ρ(M) ≈ 0.95–0.999 round counts) disappears. With ``tol > 0`` the
       solve runs round-chunked — θ surfaces every `chunk_rounds` rounds
       for the on-device convergence check. Pinned by
       `tests/test_kernels_dekrr_solve.py`.

   Every beyond-paper acceleration (Chebyshev semi-iteration in
   `repro.core.acceleration`, its power-iteration spectral estimates)
   builds on this round via the same ``backend`` switch.

3. **SPMD nodes-on-devices execution** (`make_spmd_solver`): the same round
   under `shard_map` on a 1-D device mesh, one node per device, exchanging
   only θ per round — the paper's communication pattern made literal:

     * ``mode="ppermute"``: for circulant topologies C_J(s_1, s_2, …) the
       neighbor slots are laid out ``[(+s_1), (−s_1), (+s_2), (−s_2), …]``
       and each round issues one `lax.ppermute` ring shift per slot. This
       is the TPU/ICI-native exchange: Σ_j |N_j| · D_max words per round,
       nearest-neighbor only, no gather of the full network state.
     * ``mode="allgather"``: `lax.all_gather` of θ followed by a local
       slot-table gather. Works for arbitrary connected graphs (star,
       Erdős–Rényi, …) at the cost of J·(J−1)·D_max words per round.

   Both modes accept the same ``backend`` switch: "xla" runs `_node_step`
   per device; "pallas" runs the fused kernel on the device-local [1 + K,
   D_max] θ table ``[own θ; received neighbor θs]`` (the kernel's
   `self_idx` indirection exists exactly so the J-node and 1-node-per-
   device layouts share one kernel). Parity across all paths holds at near
   machine precision.

`comm_bytes_per_round` exposes the §II-C cost model for both modes so
benchmarks can report paper-comparable communication totals.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec

from repro.analysis.vmem import check_index_table
from repro.obs.spans import count, h2d_nbytes, is_recording, span
from repro.obs.trace import SolveTrace

__all__ = [
    "PackedProblem",
    "pack_problem",
    "pack_theta",
    "unpack_theta",
    "step_batched",
    "solve_batched",
    "make_spmd_solver",
    "comm_bytes_per_round",
]


# --------------------------------------------------------------------------
# Packed problem container
# --------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedProblem:
    """Eq. 17 auxiliaries padded to [J, D_max, …] with a neighbor slot table.

    Attributes (array leaves; J nodes, K neighbor slots, D_max features):
      g:          [J, D_max, D_max]     padded G_j (Eq. 17 inverse, applied).
      d:          [J, D_max]            padded d_j — or [J, D_max, Dy] for
                                        multi-output targets; θ and every
                                        stage label share d's shape, with
                                        the trailing output axis riding
                                        through the iteration unchanged
                                        (the Eq. 17 matrices are
                                        features-only).
      s:          [J, D_max, D_max]     padded S_j.
      p:          [J, K, D_max, D_max]  padded P_{j, nbr_idx[j, k]}; the
                                        [k] slice is the zero matrix for
                                        masked (padding) slots.
      theta_mask: [J, D_max]            1.0 where coordinate i < D_j.
      nbr_idx:    [J, K] int32          global node id feeding slot k of
                                        node j (j itself on padded slots).
      nbr_mask:   [J, K]                1.0 on live slots.

    Static (hashable aux data — part of the jit cache key):
      offsets:    circulant shift set (s_1, s_2, …) when the slot table is
                  laid out in ppermute order [(+s_1), (−s_1), (+s_2), …];
                  None for the generic padded-adjacency layout.
      node_dims:  per-node feature counts (D_1, …, D_J) for unpacking.
      num_edges_directed: live (directed) slot count Σ_j |N_j|, recorded
                  from the NumPy-side nbr_mask at packing time so the
                  §II-C comm cost model never has to read it back off
                  the device (`comm_bytes_per_round`).
    """

    g: jax.Array
    d: jax.Array
    s: jax.Array
    p: jax.Array
    theta_mask: jax.Array
    nbr_idx: jax.Array
    nbr_mask: jax.Array
    offsets: tuple[int, ...] | None = None
    node_dims: tuple[int, ...] | None = None
    num_edges_directed: int | None = None

    # -- pytree plumbing (offsets / node_dims / edge count are static) ------
    def tree_flatten(self):
        children = (self.g, self.d, self.s, self.p, self.theta_mask,
                    self.nbr_idx, self.nbr_mask)
        return children, (self.offsets, self.node_dims,
                          self.num_edges_directed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        offsets, node_dims, num_edges_directed = aux
        g, d, s, p, theta_mask, nbr_idx, nbr_mask = children
        return cls(g=g, d=d, s=s, p=p, theta_mask=theta_mask,
                   nbr_idx=nbr_idx, nbr_mask=nbr_mask,
                   offsets=offsets, node_dims=node_dims,
                   num_edges_directed=num_edges_directed)

    @property
    def num_nodes(self) -> int:
        return self.d.shape[0]

    @property
    def max_features(self) -> int:
        return self.d.shape[1]

    @property
    def num_slots(self) -> int:
        return self.nbr_idx.shape[1]

    @property
    def num_outputs(self) -> int:
        """Dy — trailing output width (1 for scalar-target packings)."""
        return self.d.shape[2] if self.d.ndim == 3 else 1


def _circulant_slot_table(
    offsets: Sequence[int], num_nodes: int
) -> np.ndarray:
    """Slot table in ppermute order [(+s_1), (−s_1), (+s_2), (−s_2), …]."""
    idx = np.zeros((num_nodes, 2 * len(offsets)), dtype=np.int32)
    for m, s in enumerate(offsets):
        for j in range(num_nodes):
            idx[j, 2 * m] = (j + s) % num_nodes
            idx[j, 2 * m + 1] = (j - s) % num_nodes
    return idx


def _validate_slot_table(nbr_idx, nbr_mask, num_nodes: int) -> int:
    """Static validation of a NumPy-staged slot table; returns the live
    directed-edge count Σ_j |N_j|.

    The Pallas kernels read `nbr_idx` through scalar prefetch, which has
    no hardware bounds check — an out-of-range entry silently gathers an
    arbitrary θ-table row. Every entry (padded slots carry an in-range
    self index by construction) must lie in [0, J).
    """
    idx = np.asarray(nbr_idx)
    mask = np.asarray(nbr_mask)
    if idx.shape != mask.shape:
        raise ValueError(
            f"slot table shape mismatch: nbr_idx {idx.shape} vs "
            f"nbr_mask {mask.shape}")
    check_index_table("nbr_idx", idx, num_nodes)
    return int(np.count_nonzero(mask))


def _slot_table(solver):
    """(nbr_idx [J, K] int32, nbr_mask [J, K] float, offsets | None).

    Circulant topologies get the ppermute slot layout (and `offsets`
    recorded) whenever every node's ±s neighbors are distinct, i.e. the
    uniform degree equals 2·|offsets|; anything else — star, Erdős–Rényi,
    or a circulant with an s = J/2 self-paired shift — falls back to the
    generic padded adjacency table from `Topology.neighbor_table()`.
    """
    topo = solver.topology
    dtype = np.asarray(solver.data[0].x).dtype
    offsets = topo.circulant_offsets
    if offsets is not None and topo.max_degree == 2 * len(offsets):
        nbr_idx = _circulant_slot_table(offsets, topo.num_nodes)
        nbr_mask = np.ones(nbr_idx.shape, dtype=dtype)
        return nbr_idx, nbr_mask, tuple(int(s) for s in offsets)
    nbr_idx, live = topo.neighbor_table()
    return nbr_idx, live.astype(dtype), None


_PACK_METHODS = ("batched", "aux")


def pack_problem(solver, *, method: str = "batched",
                 gram_backend: str | None = None) -> PackedProblem:
    """Build a `PackedProblem` from a `DeKRRSolver`.

    ``method="batched"`` (default) computes the Eq. 17 auxiliaries directly
    in the padded layout: one vmapped featurize→Gram→inverse program over
    numpy-staged [J, …] inputs, traced once per problem shape — no per-node
    Python iteration over traced computation, so packing scales to large J
    (construct the solver with ``build_aux=False`` to skip the ragged
    reference build entirely). ``gram_backend`` picks how the Z Zᵀ blocks
    are computed: "xla" (batched GEMM) or "pallas" (the fused streaming
    `repro.kernels.rff_gram` kernel; default on TPU, cos_bias maps only).

    ``method="aux"`` copies the solver's ragged reference auxiliaries
    (`solver.aux`, the per-node loop) — bit-identical to the reference, and
    the only path that honors a custom ``gram_fn`` or mixed feature kinds.
    """
    if method not in _PACK_METHODS:
        raise ValueError(f"method must be one of {_PACK_METHODS}, "
                         f"got {method!r}")
    if gram_backend not in (None, "xla", "pallas"):
        raise ValueError(f"unknown gram_backend {gram_backend!r}")
    kinds = {fm.kind for fm in solver.feature_maps}
    has_bags = any(nd.bags is not None for nd in solver.data)
    if method == "batched" and (
            len(kinds) > 1                       # mixed cos_sin/cos_bias
            or getattr(solver, "_gram_fn", None) is not None
            or has_bags):
        reason = ("the solver has a custom gram_fn"
                  if getattr(solver, "_gram_fn", None) is not None
                  else "the solver has aggregate-observation (bagged) "
                       "nodes, whose Agg operator only the ragged build "
                       "applies"
                  if has_bags
                  else f"the solver mixes feature kinds {sorted(kinds)}")
        if gram_backend == "pallas":
            raise ValueError(
                f"pack_problem(gram_backend='pallas') is impossible here: "
                f"{reason}, which only the ragged method='aux' build "
                f"honors — and the aux path computes its Gram blocks "
                f"through the solver, ignoring gram_backend. Drop "
                f"gram_backend or use a uniform cos_bias solver without "
                f"gram_fn.")
        warnings.warn(
            f"pack_problem(method='batched') downgraded to method='aux': "
            f"{reason}. The aux build runs a per-node Python loop over "
            f"traced computation (re-traces with J) — expect it to be "
            f"slow at scale.", UserWarning, stacklevel=2)
        method = "aux"          # only the ragged build honors those
    if method == "aux":
        if gram_backend == "pallas":
            raise ValueError(
                "pack_problem(method='aux') copies the solver's ragged "
                "reference auxiliaries and ignores gram_backend — "
                "gram_backend='pallas' would silently not be honored. "
                "Use method='batched' for the Pallas streaming Gram path.")
        with span("pack_problem", nodes=solver.J, method="aux"):
            return _pack_problem_from_aux(solver)
    with span("pack_problem", nodes=solver.J, method="batched"):
        staged = _stage_packed_inputs(solver, gram_backend=gram_backend)
        return _finish_packed(staged, _build_packed_aux(**staged))


def _pack_problem_from_aux(solver) -> PackedProblem:
    """Legacy packing: per-node Python loop copying `solver.aux` (ragged)."""
    j_nodes = solver.J
    dims = tuple(fm.num_features for fm in solver.feature_maps)
    d_max = max(dims)
    dtype = np.asarray(solver.aux.d[0]).dtype
    nbr_idx, nbr_mask, offsets = _slot_table(solver)
    num_edges = _validate_slot_table(nbr_idx, nbr_mask, j_nodes)
    k_slots = nbr_idx.shape[1]

    g = np.zeros((j_nodes, d_max, d_max), dtype=dtype)
    # d_j is [D_j] or [D_j, Dy]; the packed stage labels carry the same
    # trailing output axis.
    out_tail = np.asarray(solver.aux.d[0]).shape[1:]
    d = np.zeros((j_nodes, d_max) + out_tail, dtype=dtype)
    s = np.zeros((j_nodes, d_max, d_max), dtype=dtype)
    p = np.zeros((j_nodes, k_slots, d_max, d_max), dtype=dtype)
    theta_mask = np.zeros((j_nodes, d_max), dtype=dtype)

    for j in range(j_nodes):
        dj = dims[j]
        g[j, :dj, :dj] = np.asarray(solver.aux.g[j])
        d[j, :dj] = np.asarray(solver.aux.d[j])
        s[j, :dj, :dj] = np.asarray(solver.aux.s[j])
        theta_mask[j, :dj] = 1.0
        for k in range(k_slots):
            if not nbr_mask[j, k]:
                continue
            nb = int(nbr_idx[j, k])
            pjp = np.asarray(solver.aux.p[j][nb])      # [D_j, D_nb]
            p[j, k, :pjp.shape[0], :pjp.shape[1]] = pjp

    return PackedProblem(
        g=jnp.asarray(g), d=jnp.asarray(d), s=jnp.asarray(s),
        p=jnp.asarray(p), theta_mask=jnp.asarray(theta_mask),
        nbr_idx=jnp.asarray(nbr_idx), nbr_mask=jnp.asarray(nbr_mask),
        offsets=offsets, node_dims=dims, num_edges_directed=num_edges,
    )


# --------------------------------------------------------------------------
# Batched Eq. 17 aux build (default pack_problem path)
# --------------------------------------------------------------------------
# Number of times the batched builder has been *traced* (not called) — the
# regression test asserts this does not grow with J or with repeat packing.
_PACK_TRACE_COUNT = 0


def pack_trace_count() -> int:
    return _PACK_TRACE_COUNT


def _stage_feature_maps(fmaps, dtype) -> dict:
    """Numpy-stage a uniform-kind feature-map list into padded [J, …]
    arrays: omega [J, F_max, d], bias [J, F_max], feat_idx [J, D_max]
    (row map from raw featurize space — size F_max or 2·F_max — into the
    packed feature space: identity for cos_bias; for cos_sin node j's
    live rows are [0, F_j) ∪ [F_max, F_max + F_j) made contiguous),
    feat_mask [J, D_max], and the per-node scale (√(2/F_j) or 1/√F_j).

    Shared by `pack_problem`'s batched build and `repro.stream` — the
    stream's rtol-1e-9 parity contract depends on bit-identical staging,
    so there is exactly one copy of these conventions.
    """
    kinds = {fm.kind for fm in fmaps}
    if len(kinds) > 1:
        raise ValueError(
            f"feature-map staging requires a uniform kind across nodes "
            f"(got {sorted(kinds)}) — mixed kinds are only supported by "
            f"the ragged pack_problem(method='aux') path")
    kind = fmaps[0].kind
    j_nodes = len(fmaps)
    dim_in = fmaps[0].omega.shape[1]
    freqs = np.array([fm.num_frequencies for fm in fmaps])
    dims = np.array([fm.num_features for fm in fmaps])
    f_max, d_max = int(freqs.max()), int(dims.max())

    omega = np.zeros((j_nodes, f_max, dim_in), dtype=dtype)
    bias = np.zeros((j_nodes, f_max), dtype=dtype)
    for j, fm in enumerate(fmaps):
        omega[j, :freqs[j]] = np.asarray(fm.omega)
        if fm.bias is not None:
            bias[j, :freqs[j]] = np.asarray(fm.bias)
    feat_mask = (np.arange(d_max)[None, :] < dims[:, None]).astype(dtype)
    if kind == "cos_bias":
        feat_idx = np.broadcast_to(np.arange(d_max, dtype=np.int32),
                                   (j_nodes, d_max)).copy()
        scale = np.sqrt(2.0 / freqs).astype(dtype)
    else:
        feat_idx = np.zeros((j_nodes, d_max), dtype=np.int32)
        for j, fj in enumerate(freqs):
            feat_idx[j, :2 * fj] = np.concatenate(
                [np.arange(fj), f_max + np.arange(fj)])
        scale = (1.0 / np.sqrt(freqs)).astype(dtype)
    return dict(omega=omega, bias=bias, feat_idx=feat_idx,
                feat_mask=feat_mask, scale=scale, kind=kind,
                node_dims=tuple(int(v) for v in dims))


def _to_device(*arrays) -> list:
    """`jnp.asarray` of each array, the numpy bytes copied counted as
    `pack.h2d_bytes` while recording."""
    if is_recording():
        count("pack.h2d_bytes", h2d_nbytes(*arrays))
    return [jnp.asarray(a) for a in arrays]


def _padded_layout(solver) -> dict:
    """The shape the batched pack pads every node to, as the `pack.stage`
    span records it: J nodes, K neighbour slots (the topology's largest
    degree), F_max frequencies, D_max features and N_max samples."""
    fmaps = solver.feature_maps
    return dict(nodes=solver.J, slots=solver.topology.max_degree,
                f_max=max(fm.num_frequencies for fm in fmaps),
                d_max=max(fm.num_features for fm in fmaps),
                n_max=max(nd.num_samples for nd in solver.data))


def _stage_packed_inputs(solver, *, gram_backend: str | None) -> dict:
    """Numpy-stage padded [J, …] inputs for the batched Eq. 17 build.

    All cross-node gathering (neighbor Ω/b/X/masks by slot) happens here
    with vectorized fancy indexing, so the traced builder is a pure vmap
    over the leading node axis — which is what makes the per-node batch-of-1
    replay in the regression test bit-identical to the batched call.
    """
    if gram_backend is None:
        gram_backend = "pallas" if jax.default_backend() == "tpu" else "xla"
    with span("pack.stage",
              **(_padded_layout(solver) if is_recording() else {})):
        j_nodes = solver.J
        dtype = np.asarray(solver.data[0].x).dtype

        maps = _stage_feature_maps(solver.feature_maps, dtype)
        kind = maps["kind"]
        omega, bias = maps["omega"], maps["bias"]
        feat_idx, feat_mask = maps["feat_idx"], maps["feat_mask"]
        scale = maps["scale"]
        sizes = np.array([nd.num_samples for nd in solver.data])
        n_max = int(sizes.max())
        dim_in = solver.data[0].x.shape[0]

        x = np.zeros((j_nodes, dim_in, n_max), dtype=dtype)
        dy = solver.data[0].num_outputs if solver.data[0].y.ndim > 1 else None
        y = np.zeros((j_nodes, n_max) if dy is None else (j_nodes, n_max, dy),
                     dtype=dtype)
        for j, nd in enumerate(solver.data):
            x[j, :, :sizes[j]] = np.asarray(nd.x)
            if dy is None:
                y[j, :sizes[j]] = np.asarray(nd.y).reshape(-1)
            else:
                y[j, :sizes[j]] = np.asarray(nd.y)
        col_mask = (np.arange(n_max)[None, :] < sizes[:, None]).astype(dtype)

        ct_self, ct_nei = solver.coupling_coefficients()
        degs = solver.topology.degrees.astype(dtype)
        nbr_idx, nbr_mask, offsets = _slot_table(solver)

        gather = lambda a: a[nbr_idx]            # [J, K, …] by slot table
        staged = dict(
            omega=omega, bias=bias, x=x, y=y,
            col_mask=col_mask, feat_mask=feat_mask, feat_idx=feat_idx,
            scale=scale,
            omega_n=gather(omega), bias_n=gather(bias), x_n=gather(x),
            col_mask_n=gather(col_mask), feat_mask_n=gather(feat_mask),
            feat_idx_n=gather(feat_idx), scale_n=gather(scale),
            ct_self=ct_self.astype(dtype), ct_nei=ct_nei.astype(dtype),
            ct_nei_n=gather(ct_nei.astype(dtype)),
            degree=degs, nbr_mask=nbr_mask.astype(dtype),
            lam_over_j=np.full((j_nodes,),
                               solver.config.lam / solver.J, dtype=dtype),
            n_total=np.full((j_nodes,), float(solver.N), dtype=dtype),
            kind=kind,
        )
    if gram_backend == "pallas" and kind == "cos_bias" and j_nodes > 0:
        with span("pack.gram"):
            staged.update(_pallas_gram_blocks(staged))
    # bookkeeping for _finish_packed (not builder inputs)
    staged["_meta"] = (maps["node_dims"], nbr_idx, offsets)
    return staged


def _pallas_gram_blocks(staged: dict) -> dict:
    """Route the Eq. 17 Z Zᵀ blocks through the fused streaming Pallas
    kernel (`repro.kernels.ops.rff_gram_batched`), unit-scale frequency
    space: gram_jj/zy for every node and Gram(Z_{j,p}) for every slot.
    Per-node √(2/D_j) scaling and feature masking happen in `_node_aux`.
    """
    from repro.kernels.ops import rff_gram_batched

    omega, bias = staged["omega"], staged["bias"]
    x, y, cm = staged["x"], staged["y"], staged["col_mask"]
    j_nodes, k_slots = staged["nbr_mask"].shape
    # The streaming kernel's zy accumulator is scalar-target only; for
    # multi-output ([J, n_max, Dy]) y the label term is formed in
    # `_node_aux` from the packed features instead, and the kernel only
    # supplies the Gram blocks.
    y_kernel = y if y.ndim == 2 else np.zeros(y.shape[:2], x.dtype)
    graw, zyraw = rff_gram_batched(*_to_device(omega, bias, x, y_kernel, cm))
    f_max, dim_in = omega.shape[1:]
    if k_slots == 0:
        gcross = np.zeros((j_nodes, 0, f_max, f_max), x.dtype)
        return dict(gram_raw=np.asarray(graw), zy_raw=np.asarray(zyraw),
                    gram_cross_raw=gcross)
    # Z_{j,p}: node j's map on each slot-neighbor's data, flattened (j, k)
    om_rep = np.broadcast_to(omega[:, None], (j_nodes, k_slots) +
                             omega.shape[1:]).reshape(-1, f_max, dim_in)
    bi_rep = np.broadcast_to(bias[:, None],
                             (j_nodes, k_slots, f_max)).reshape(-1, f_max)
    x_n = staged["x_n"].reshape((-1,) + x.shape[1:])
    cm_n = staged["col_mask_n"].reshape(-1, cm.shape[1])
    om_rep, bi_rep, x_n, cm_n = _to_device(om_rep, bi_rep, x_n, cm_n)
    gcross, _ = rff_gram_batched(om_rep, bi_rep, x_n,
                                 jnp.zeros(cm_n.shape, x.dtype), cm_n)
    return dict(
        gram_raw=np.asarray(graw), zy_raw=np.asarray(zyraw),
        gram_cross_raw=np.asarray(gcross).reshape(
            j_nodes, k_slots, f_max, f_max))


def _gauss_jordan_inv(a: jax.Array) -> jax.Array:
    """Unpivoted Gauss-Jordan inverse (safe: Eq. 17's matrix is SPD, and the
    padding is an identity block). Used instead of `jnp.linalg.inv` because
    LAPACK's blocked getrf rounds differently at different batch sizes —
    this form is built from batch-invariant elementwise ops, which is what
    lets the per-node regression replay match the batched build bit-for-bit
    (accuracy is Cholesky-grade on SPD inputs, ~1e-15 residual)."""
    dim = a.shape[0]
    aug = jnp.concatenate([a, jnp.eye(dim, dtype=a.dtype)], axis=1)

    def body(i, aug):
        piv = aug[i] / aug[i, i]
        aug = aug - jnp.outer(aug[:, i], piv)
        return aug.at[i].set(piv)

    aug = jax.lax.fori_loop(0, dim, body, aug)
    return aug[:, dim:]


def _featurize_raw(omega, bias, x, kind):
    """Unscaled raw features on one node: [F, dim] × [dim, N] → [R, N]."""
    proj = jnp.einsum("fd,dn->fn", omega, x,
                      precision=jax.lax.Precision.HIGHEST)
    if kind == "cos_bias":
        return jnp.cos(proj + bias[:, None])
    return jnp.concatenate([jnp.cos(proj), jnp.sin(proj)], axis=0)


def _node_aux(omega, bias, x, y, col_mask, feat_mask, feat_idx, scale,
              omega_n, bias_n, x_n, col_mask_n, feat_mask_n, feat_idx_n,
              scale_n, ct_self, ct_nei, ct_nei_n, degree, nbr_mask,
              lam_over_j, n_total, *, kind,
              gram_raw=None, zy_raw=None, gram_cross_raw=None):
    """Eq. 17 auxiliaries for ONE node in the padded layout (vmapped over
    the node axis by `_build_packed_aux`). All neighbor inputs arrive
    pre-gathered per slot ([K, …]); masked slots carry nbr_mask 0 and the
    node's own arrays, so their contributions cancel exactly.
    """
    hi = jax.lax.Precision.HIGHEST
    pack = lambda raw, idx, fm, sc, cm: (            # raw [R, N] → Z [D, N]
        jnp.take(raw, idx, axis=0) * sc * fm[:, None] * cm[None, :])

    z = pack(_featurize_raw(omega, bias, x, kind),
             feat_idx, feat_mask, scale, col_mask)          # Z_jj [D, N]
    # neighbor maps on own data / own map on neighbor data / neighbor-own
    raw_n_on_j = jax.vmap(
        lambda om, b: _featurize_raw(om, b, x, kind))(omega_n, bias_n)
    z_n_on_j = jax.vmap(pack)(
        raw_n_on_j, feat_idx_n, feat_mask_n, scale_n,
        jnp.broadcast_to(col_mask, (omega_n.shape[0],) + col_mask.shape))
    raw_j_on_n = jax.vmap(
        lambda xn: _featurize_raw(omega, bias, xn, kind))(x_n)
    z_j_on_n = jax.vmap(
        lambda raw, cm: pack(raw, feat_idx, feat_mask, scale, cm))(
            raw_j_on_n, col_mask_n)
    raw_nn = jax.vmap(
        lambda om, b, xn: _featurize_raw(om, b, xn, kind))(
            omega_n, bias_n, x_n)
    z_nn = jax.vmap(pack)(raw_nn, feat_idx_n, feat_mask_n, scale_n,
                          col_mask_n)                       # Z_pp [K, D, N]

    if y.ndim == 1:
        # mult+sum rather than a matvec: XLA's gemv rounds differently at
        # different batch sizes, this form is batch-invariant (regression
        # replay in tests/test_dist_property.py)
        d_vec_z = jnp.sum(z * y[None, :], axis=1) / n_total
    else:
        # multi-output: same batch-invariant mult+sum per output column
        d_vec_z = jnp.sum(z[:, :, None] * y[None, :, :], axis=1) / n_total

    if gram_raw is not None:
        # Pallas streaming kernel output (unit-scale frequency space ==
        # packed feature space for cos_bias); mask + scale here. The
        # kernel's zy accumulator only exists for scalar targets.
        fouter = feat_mask[:, None] * feat_mask[None, :]
        gram_jj = gram_raw * scale**2 * fouter
        d_vec = (zy_raw * scale * feat_mask / n_total
                 if y.ndim == 1 else d_vec_z)
        gram_cross = (gram_cross_raw * scale**2 * fouter[None])
    else:
        gram_jj = jnp.einsum("an,bn->ab", z, z, precision=hi)
        d_vec = d_vec_z
        gram_cross = jnp.einsum("kan,kbn->kab", z_j_on_n, z_j_on_n,
                                precision=hi)

    a = (1.0 / n_total + 2.0 * ct_self + degree * ct_nei) * gram_jj
    a = a + lam_over_j * jnp.diag(feat_mask)
    a = a + jnp.einsum("k,kab->ab", nbr_mask * ct_nei_n, gram_cross,
                       precision=hi)
    g = _gauss_jordan_inv(a + jnp.diag(1.0 - feat_mask))
    g = g * feat_mask[:, None] * feat_mask[None, :]

    s = 2.0 * ct_self * gram_jj
    p = (ct_nei * jnp.einsum("an,kbn->kab", z, z_n_on_j, precision=hi)
         + ct_nei_n[:, None, None]
         * jnp.einsum("kan,kbn->kab", z_j_on_n, z_nn, precision=hi))
    p = p * nbr_mask[:, None, None]
    return g, d_vec, s, p


@partial(jax.jit, static_argnames=("kind",))
def _vmapped_node_aux(kind, **arrays):
    global _PACK_TRACE_COUNT
    _PACK_TRACE_COUNT += 1          # Python side effect: counts traces only
    return jax.vmap(partial(_node_aux, kind=kind))(**arrays)


def _build_packed_aux(*, kind, _meta=None, **staged):
    """One traced program for the whole network (trace count independent of
    J) — see `_vmapped_node_aux` for the counter the regression test pins."""
    return _vmapped_node_aux(kind=kind, **dict(zip(
        staged, _to_device(*staged.values()))))


def _finish_packed(staged: dict, built) -> PackedProblem:
    g, d, s, p = built
    dims, nbr_idx, offsets = staged["_meta"]
    num_edges = _validate_slot_table(nbr_idx, staged["nbr_mask"], len(dims))
    theta_mask, nbr_idx_dev, nbr_mask = _to_device(
        staged["feat_mask"], nbr_idx, staged["nbr_mask"])
    return PackedProblem(
        g=g, d=d, s=s, p=p,
        theta_mask=theta_mask, nbr_idx=nbr_idx_dev, nbr_mask=nbr_mask,
        offsets=offsets, node_dims=dims, num_edges_directed=num_edges,
    )


def _pack_problem_pernode(solver, *, gram_backend: str | None = None
                          ) -> PackedProblem:
    """The removed per-node Python loop, kept as the regression target: the
    same staged inputs and the same vmapped program, but replayed one
    batch-of-1 call per node. `pack_problem(method="batched")` must produce
    bit-identical contents (tests/test_dist_property.py)."""
    staged = _stage_packed_inputs(solver, gram_backend=gram_backend)
    meta, kind = staged.pop("_meta"), staged.pop("kind")
    parts = [
        _build_packed_aux(kind=kind, **{k: v[j:j + 1]
                                        for k, v in staged.items()})
        for j in range(solver.J)
    ]
    built = tuple(jnp.concatenate(col, axis=0) for col in zip(*parts))
    staged.update(_meta=meta, kind=kind)
    return _finish_packed(staged, built)


def pack_theta(packed: PackedProblem,
               theta: Sequence[jax.Array]) -> jax.Array:
    """Ragged per-node θ list → padded [J, D_max] (or [J, D_max, Dy]).

    Vectors shorter than their node's D_j re-pad with exact zeros, so a θ
    taken from a packing whose dims have since *grown* (e.g. a per-node
    DDRF feature refresh in `repro.stream` that enlarged D_j) round-trips
    cleanly. Vectors *longer* than D_j (from `packed.node_dims`, or D_max
    when dims were not recorded) are rejected with a clear error — such a
    θ is stale against this layout, and padding it would either crash
    deep in `jnp.pad` with a negative pad width or silently put mass on
    padded coordinates the iteration treats as dead. The output width is
    validated the same way: every θ_j must be [D_j]-shaped for a
    scalar-target packing and [D_j, Dy]-shaped (with THIS packing's Dy)
    for a multi-output one — a θ from a packing with a different Dy is
    stale, and reshaping it would silently scramble output columns.
    """
    theta = list(theta)
    if len(theta) != packed.num_nodes:
        raise ValueError(
            f"pack_theta got {len(theta)} θ vectors for a packed problem "
            f"with {packed.num_nodes} nodes")
    d_max = packed.max_features
    out_tail = packed.d.shape[2:]            # () scalar, (Dy,) multi-output
    for j, t in enumerate(theta):
        if t.shape[1:] != out_tail:
            want = (f"[D_j, Dy={out_tail[0]}]" if out_tail
                    else "[D_j] (scalar targets)")
            raise ValueError(
                f"theta[{j}] has shape {tuple(t.shape)} but this packing "
                f"carries {want} per-node θ — this θ was packed under a "
                f"different output width Dy and cannot be re-laid-out "
                f"silently. Re-derive it for the current targets.")
        limit = (packed.node_dims[j] if packed.node_dims is not None
                 else d_max)
        if t.shape[0] > limit:
            raise ValueError(
                f"theta[{j}] has {t.shape[0]} coordinates but node {j} "
                f"has D_j = {limit} (D_max = {d_max}) — this θ is stale "
                f"against the packed layout (was node {j}'s feature map "
                f"refreshed to fewer features?). Re-derive it for the "
                f"current dims (repro.stream.repad_theta re-pads carried "
                f"iterates across a refresh).")
    pad_tail = ((0, 0),) * len(out_tail)
    return jnp.stack([jnp.pad(t, ((0, d_max - t.shape[0]),) + pad_tail)
                      for t in theta])


def unpack_theta(packed: PackedProblem,
                 theta: jax.Array) -> list[jax.Array]:
    """Padded [J, D_max] (or [J, D_max, Dy]) θ → ragged per-node list.

    Validates θ against the packed layout — BOTH the feature width and
    the output width: a θ from a different packing (carried across a
    `repro.stream` feature refresh that changed D_max, or packed under a
    different Dy) must not be sliced silently — slicing a too-narrow θ
    would truncate node vectors, and reinterpreting a different Dy would
    scramble output columns, without any error.
    """
    if packed.node_dims is None:
        raise ValueError("packed problem has no node_dims recorded")
    want = packed.d.shape                # (J, D_max) or (J, D_max, Dy)
    if theta.shape != want:
        raise ValueError(
            f"unpack_theta got θ of shape {theta.shape} for a packed "
            f"problem of θ-shape {want} (Dy = {packed.num_outputs}) — "
            f"this θ belongs to a different packing (stale across a "
            f"feature refresh that re-padded D_max, or packed under a "
            f"different output width Dy?). Unpack it with ITS packing, "
            f"then re-pack (or use repro.stream.repad_theta).")
    return [theta[j, :dj] for j, dj in enumerate(packed.node_dims)]


# --------------------------------------------------------------------------
# One Eq. 19 round — the single arithmetic kernel shared by every runtime
# --------------------------------------------------------------------------
def _node_step(g: jax.Array, d: jax.Array, s: jax.Array, p: jax.Array,
               theta: jax.Array, nbr_theta: jax.Array,
               nbr_mask: jax.Array) -> jax.Array:
    """θ_j ← G_j (d_j + S_j θ_j + Σ_k P_{j,k} θ_{nbr(j,k)})  for one node.

    Shapes: g/s [D, D], d/theta [D] (or [D, Dy]), p [K, D, D], nbr_theta
    [K, D] (or [K, D, Dy]), nbr_mask [K]. Masked slots carry zero P
    blocks, so the mask multiply is belt-and-braces; padded coordinates
    come out exactly 0.0 because the corresponding rows of g are zero.
    The multi-output branch is the same contraction per output column —
    scalar targets keep the exact original trace. Every product runs at
    HIGHEST precision, as the Pallas round kernels do: a TPU's default
    f32 matmul rounds its operands to bf16.
    """
    hi = jax.lax.Precision.HIGHEST
    if theta.ndim == 1:
        coupled = jnp.einsum("kab,kb->a", p, nbr_theta * nbr_mask[:, None],
                             precision=hi)
    else:
        coupled = jnp.einsum("kab,kbo->ao", p,
                             nbr_theta * nbr_mask[:, None, None],
                             precision=hi)
    own = jnp.matmul(s, theta, precision=hi)
    return jnp.matmul(g, d + own + coupled, precision=hi)


_BACKENDS = ("xla", "pallas", "pallas_fused")
# Backends whose per-round arithmetic is the fused Pallas round kernel.
_PALLAS_BACKENDS = ("pallas", "pallas_fused")
# Default tol-check cadence for the fused solve: surfacing θ every round
# would defeat the whole point of fusing the scan into the kernel.
_FUSED_CHUNK_DEFAULT = 32


def _check_backend(backend: str) -> None:
    if backend not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, "
                         f"got {backend!r}")


@partial(jax.jit, static_argnames=("backend",))
def step_batched(packed: PackedProblem, theta: jax.Array,
                 backend: str = "xla", *,
                 active: jax.Array | None = None,
                 nbr_theta: jax.Array | None = None) -> jax.Array:
    """One Jacobi round of Eq. 19 over all nodes (synchronous by default).

    theta: [J, D_max] → [J, D_max] (or [J, D_max, Dy] → [J, D_max, Dy]
    for multi-output packings — the trailing output axis batches through
    the same GEMMs). Padding is preserved exactly (zero in, zero out) —
    see the module docstring for why no mask is needed.

    ``backend="xla"`` is the vmapped-GEMM round; ``backend="pallas"`` the
    fused `repro.kernels.dekrr_step` kernel (in-kernel slot-table gather, θ
    VMEM-resident; interpret-mode on CPU). ``backend="pallas_fused"`` only
    differs from "pallas" at the *solve* level (rounds fused into one
    kernel); a single step runs the same per-round kernel. All run the
    same arithmetic and agree at rtol 1e-9 under x64.

    The async-gossip runtime (`repro.dist.async_gossip`) threads two
    keyword extras through the same entry point:

      * ``active`` ([J], any dtype): nodes with active[j] == 0 pass their
        θ row through untouched — jnp.where on the XLA path, the
        activation-masked kernel variant on the Pallas paths. With
        ``active`` omitted or all-ones the synchronous arithmetic runs
        bit-for-bit.
      * ``nbr_theta`` ([J, K, D_max]): per-slot neighbor θ to couple
        against *instead of* gathering ``theta[packed.nbr_idx]`` — the
        async per-edge staleness buffers. On the Pallas paths the buffers
        are appended below θ as extra table rows ([J·(1+K), D_max]) and
        the slot table is re-pointed at them, so the kernel's gather
        semantics are unchanged.
    """
    _check_backend(backend)
    if backend in _PALLAS_BACKENDS:
        from repro.kernels.ops import dekrr_step

        j_nodes, k_slots = packed.num_nodes, packed.num_slots
        self_idx = jnp.arange(j_nodes, dtype=jnp.int32)
        if nbr_theta is None:
            table, nbr_idx = theta, packed.nbr_idx
        else:
            table = jnp.concatenate(
                [theta, nbr_theta.reshape((j_nodes * k_slots,)
                                          + theta.shape[1:])], axis=0)
            nbr_idx = j_nodes + jnp.arange(
                j_nodes * k_slots, dtype=jnp.int32).reshape(j_nodes,
                                                            k_slots)
        return dekrr_step(packed.g, packed.d, packed.s, packed.p, table,
                          nbr_idx, self_idx, packed.nbr_mask, active)
    if nbr_theta is None:
        nbr_theta = theta[packed.nbr_idx]              # [J, K, D_max]
    new = jax.vmap(_node_step)(
        packed.g, packed.d, packed.s, packed.p, theta, nbr_theta,
        packed.nbr_mask)
    if active is not None:
        gate = jnp.reshape(active != 0, (-1,) + (1,) * (theta.ndim - 1))
        new = jnp.where(gate, new, theta)
    return new


def _run_rounds(packed: PackedProblem, theta: jax.Array, num_rounds: int,
                backend: str) -> jax.Array:
    """`num_rounds` Eq. 19 rounds from `theta` — the one place the solve
    backends diverge: "pallas_fused" runs them as ONE pallas_call of the
    multi-round kernel (θ VMEM-resident across rounds, one dispatch);
    "xla"/"pallas" scan the per-round step (one dispatch per round)."""
    if num_rounds == 0:
        return theta
    if backend == "pallas_fused":
        from repro.kernels.ops import dekrr_solve

        self_idx = jnp.arange(packed.num_nodes, dtype=jnp.int32)
        return dekrr_solve(packed.g, packed.d, packed.s, packed.p, theta,
                           packed.nbr_idx, self_idx, packed.nbr_mask,
                           num_rounds=num_rounds)

    def round_fn(th, _):
        return step_batched(packed, th, backend=backend), None

    theta, _ = lax.scan(round_fn, theta, None, length=num_rounds)
    return theta


def _run_rounds_traced(packed: PackedProblem, theta: jax.Array,
                       num_rounds: int, backend: str
                       ) -> tuple[jax.Array, jax.Array]:
    """`_run_rounds` emitting the per-round residuals [num_rounds] too:
    residuals[r] = max|θ^{r+1} − θ^r| over every coordinate (padded slots
    are identically zero on both sides, so no masking is needed). On
    "pallas_fused" the per-(round, node) residual comes out of the SAME
    pallas_call as an extra [R, J] output block — still one dispatch; the
    per-round backends fold the same max into the existing scan."""
    if num_rounds == 0:
        return theta, jnp.zeros((0,), theta.dtype)
    if backend == "pallas_fused":
        from repro.kernels.ops import dekrr_solve

        self_idx = jnp.arange(packed.num_nodes, dtype=jnp.int32)
        theta, res = dekrr_solve(
            packed.g, packed.d, packed.s, packed.p, theta,
            packed.nbr_idx, self_idx, packed.nbr_mask,
            num_rounds=num_rounds, trace=True)
        return theta, jnp.max(res, axis=1)

    def round_fn(th, _):
        new = step_batched(packed, th, backend=backend)
        return new, jnp.max(jnp.abs(new - th))

    return lax.scan(round_fn, theta, None, length=num_rounds)


def solve_batched(packed: PackedProblem, num_iters: int,
                  theta0: jax.Array | None = None,
                  backend: str = "xla", *, tol: float = 0.0,
                  chunk_rounds: int | None = None,
                  return_rounds: bool = False,
                  return_trace: bool = False) -> jax.Array:
    """Run up to `num_iters` batched rounds from θ = 0 (or theta0).

    ``backend="xla"|"pallas"`` scans the per-round step (`lax.scan`, one
    kernel dispatch per round); ``backend="pallas_fused"`` runs whole
    blocks of rounds inside one `repro.kernels.dekrr_solve` pallas_call —
    the θ table stays VMEM-resident across rounds and per-round dispatch
    overhead disappears. All three agree at rtol 1e-9 under x64.

    ``tol > 0`` enables early stopping on max|θ^{k+c} − θ^k| < tol, checked
    every `chunk_rounds` rounds (default: 1 for the per-round backends —
    matching `DeKRRSolver.solve`'s per-round check — and
    ``_FUSED_CHUNK_DEFAULT`` for "pallas_fused", which only surfaces θ at
    chunk boundaries). The delta is computed on device inside the scan:
    no host synchronization per round, one device→host transfer total.
    ``chunk_rounds`` without tol forces the same round-chunked scan (used
    by the chunk-equivalence tests and benchmarks).

    ``return_rounds=True`` additionally returns the number of rounds
    actually run (an int32 scalar array; == num_iters unless tol stopped
    the solve early).

    ``return_trace=True`` appends a `repro.obs.SolveTrace` whose
    ``residuals`` is the on-device [num_iters] per-round convergence
    series residuals[r] = max|θ^{r+1} − θ^r|, recorded inside the
    existing scan/while/kernel round structure — zero host callbacks and
    zero extra kernel dispatches ("pallas_fused" reads it off an extra
    output block of the same pallas_call). Chunk-invariant: the series is
    identical for every `chunk_rounds`. On tol-stopped solves the rounds
    after the stop (frozen rounds) record 0. Return order is
    ``(theta[, rounds][, trace])``.

    The dispatch runs inside a `solve.batched` span that records the
    packed shape every round streams: `nodes` J, `slots` K, `d_max`.
    """
    layout = (dict(nodes=packed.num_nodes, slots=packed.num_slots,
                   d_max=packed.max_features) if is_recording() else {})
    with span("solve.batched", **layout):
        return _solve_batched(packed, num_iters, theta0, backend, tol=tol,
                              chunk_rounds=chunk_rounds,
                              return_rounds=return_rounds,
                              return_trace=return_trace)


@partial(jax.jit, static_argnames=("num_iters", "backend", "tol",
                                   "chunk_rounds", "return_rounds",
                                   "return_trace"))
def _solve_batched(packed: PackedProblem, num_iters: int,
                   theta0: jax.Array | None, backend: str, *, tol: float,
                   chunk_rounds: int | None, return_rounds: bool,
                   return_trace: bool):
    _check_backend(backend)
    if tol < 0:
        raise ValueError(f"tol must be >= 0, got {tol}")
    if chunk_rounds is not None and chunk_rounds < 1:
        raise ValueError(f"chunk_rounds must be >= 1, got {chunk_rounds}")
    if theta0 is None:
        theta0 = jnp.zeros_like(packed.d)
    num_iters = int(num_iters)

    def finish(theta, rounds, residuals):
        out = (theta,)
        if return_rounds:
            out += (rounds,)
        if return_trace:
            out += (SolveTrace(residuals=residuals),)
        return out if len(out) > 1 else theta

    if tol == 0.0:
        # No early stop: straight-line rounds (chunked only on request).
        run = _run_rounds_traced if return_trace else (
            lambda *a: (_run_rounds(*a), None))
        if chunk_rounds is None or chunk_rounds >= max(num_iters, 1):
            theta, res = run(packed, theta0, num_iters, backend)
        else:
            n_full, rem = divmod(num_iters, chunk_rounds)

            def chunk_fn(th, _):
                return run(packed, th, chunk_rounds, backend)

            theta, res = lax.scan(chunk_fn, theta0, None, length=n_full)
            theta, res_rem = run(packed, theta, rem, backend)
            if return_trace:
                res = jnp.concatenate([res.reshape(-1), res_rem])
        return finish(theta, jnp.asarray(num_iters, jnp.int32), res)

    chunk = chunk_rounds if chunk_rounds is not None else (
        _FUSED_CHUNK_DEFAULT if backend == "pallas_fused" else 1)
    chunk = min(chunk, max(num_iters, 1))
    n_full, rem = divmod(num_iters, chunk)

    def cond_fn(carry):
        _, rounds, converged = carry[:3]
        return jnp.logical_not(converged) & (rounds < n_full * chunk)

    def body_fn(carry):
        th, rounds = carry[0], carry[1]
        if return_trace:
            new, chunk_res = _run_rounds_traced(packed, th, chunk, backend)
            # Preallocated [num_iters] buffer; frozen rounds stay 0.
            buf = lax.dynamic_update_slice(carry[3], chunk_res, (rounds,))
            tail = (buf,)
        else:
            new = _run_rounds(packed, th, chunk, backend)
            tail = ()
        delta = jnp.max(jnp.abs(new - th))       # one fused on-device delta
        return (new, rounds + chunk, delta < tol) + tail

    init = (theta0, jnp.asarray(0, jnp.int32), jnp.asarray(False))
    if return_trace:
        init += (jnp.zeros((num_iters,), theta0.dtype),)
    carry = lax.while_loop(cond_fn, body_fn, init)
    theta, rounds, converged = carry[:3]
    res_buf = carry[3] if return_trace else None
    if rem:
        if return_trace:
            def rem_fn(op):
                th, buf, rd = op
                new, r = _run_rounds_traced(packed, th, rem, backend)
                return new, lax.dynamic_update_slice(buf, r, (rd,))

            theta, res_buf = lax.cond(
                converged, lambda op: (op[0], op[1]), rem_fn,
                (theta, res_buf, rounds))
        else:
            theta = lax.cond(
                converged, lambda th: th,
                lambda th: _run_rounds(packed, th, rem, backend), theta)
        rounds = jnp.where(converged, rounds, rounds + rem)
    return finish(theta, rounds, res_buf)


# --------------------------------------------------------------------------
# SPMD nodes-on-devices runtime
# --------------------------------------------------------------------------
_MODES = ("ppermute", "allgather")


def _make_exchange(mode: str, axis_name: str, j_nodes: int,
                   offsets: tuple[int, ...] | None, nbr_idx: jax.Array):
    """Per-device neighbor exchange ``vec [1, W] → [K, W]`` for a
    shard_map node program — the one collective wiring the sync and async
    SPMD solvers share (their bit-for-bit equivalence at full activation
    rests on it, so there is exactly one copy).

    ``"ppermute"``: one fwd + one bwd ring shift per circulant offset, in
    the packed slot order [(+s_1), (−s_1), (+s_2), (−s_2), …].
    ``"allgather"``: gather every device's row 0, then take this node's
    slots. ``nbr_idx`` is the device-local [1, K] slot-table operand.
    """
    def exchange(vec):
        if mode == "ppermute":
            recvs = []
            for shift in offsets:
                # receive from node (j+shift): source (i+shift) -> dest i
                fwd = lax.ppermute(
                    vec, axis_name,
                    [(i, (i - shift) % j_nodes) for i in range(j_nodes)])
                # receive from node (j-shift): source (i-shift) -> dest i
                bwd = lax.ppermute(
                    vec, axis_name,
                    [(i, (i + shift) % j_nodes) for i in range(j_nodes)])
                recvs.extend((fwd, bwd))
            return jnp.concatenate(recvs, axis=0)
        everyone = lax.all_gather(vec[0], axis_name)         # [J, W]
        return jnp.take(everyone, nbr_idx[0], axis=0)

    return exchange


def _check_spmd_problem(packed: PackedProblem, mesh: Mesh, axis_name: str,
                        mode: str) -> None:
    """Shared launch-time validation for the sync and async SPMD solvers:
    one node per device along the axis, and circulant slot layout when the
    exchange is ppermute ring shifts."""
    j_nodes = packed.num_nodes
    if mesh.shape[axis_name] != j_nodes:
        raise ValueError(
            f"mesh axis {axis_name!r} has {mesh.shape[axis_name]} "
            f"devices but the problem has {j_nodes} nodes")
    if mode == "ppermute":
        if packed.offsets is None:
            raise ValueError(
                "ppermute mode needs a circulant-packed problem "
                "(packed.offsets is None — use mode='allgather')")
        if packed.num_slots != 2 * len(packed.offsets):
            raise ValueError("slot table is not in circulant layout")


def make_spmd_solver(mesh: Mesh, axis_name: str, mode: str = "ppermute",
                     backend: str = "xla"):
    """Build `run(packed, num_iters) -> [J, D_max]` on a 1-D node mesh.

    One node per device along `axis_name`; device index along the axis IS
    the node id, so `pack_problem`'s slot table and the mesh agree by
    construction. Per round only θ moves between devices:

      * ``"ppermute"``  — one `lax.ppermute` ring shift per circulant slot
        (requires a circulant-packed problem, `packed.offsets` not None);
        Σ_j |N_j| · D_max words per round.
      * ``"allgather"`` — `lax.all_gather` θ then gather slots locally;
        any topology; J·(J−1)·D_max words per round.

    ``backend`` picks the per-device arithmetic through the same switch as
    `step_batched`/`solve_batched`: "xla" runs `_node_step` (identical to
    `step_batched`); "pallas" runs the fused `repro.kernels.dekrr_step`
    kernel on the local θ table ``[own θ; received neighbor θs]`` with
    `self_idx = [0]` — the same kernel as the batched runtime, which is
    what makes rtol-1e-9 parity hold everywhere. "pallas_fused" is
    accepted for plumbing uniformity but runs the per-round kernel too:
    each SPMD round is bounded by the inter-device θ exchange
    (ppermute/all_gather), so rounds cannot be fused across the
    collective — cross-round fusion exists only in the single-core
    batched runtime (`solve_batched(backend="pallas_fused")`).

    The returned runner is
    ``run(packed, num_iters, theta0=None, *, tol=0.0,
    return_rounds=False)``:

      * ``theta0`` ([J, D_max], sharded like θ) warm-starts the iteration
        — the `repro.stream` runtime's carried iterate; None runs from
        zeros exactly as before.
      * ``tol > 0`` enables per-round early stopping: each device reduces
        its local max|Δθ| and a fused `lax.pmax` over the node axis makes
        every device see the NETWORK-wide delta, so every per-device
        `lax.while_loop` takes the same trip decision and the in-body
        collectives stay matched. The exit is genuine — after the
        converging round no further compute OR exchange runs, so a
        converged solve stops paying for the budget's tail; θ and the
        round count exactly match
        ``solve_batched(..., tol=tol, chunk_rounds=1)``.
      * ``return_rounds=True`` appends the rounds-run int32 scalar.
      * ``return_trace=True`` appends a `repro.obs.SolveTrace` with the
        [num_iters] per-round network-wide max|Δθ| series. Each device
        records its LOCAL per-round delta into the scan/while carry (no
        extra collective); the network-wide max is reduced over the
        device axis outside the shard_map. Frozen rounds (after a tol
        stop) record 0. Return order: ``(theta[, rounds][, trace])``.
    """
    if mode not in _MODES:
        raise ValueError(f"mode must be one of {_MODES}, got {mode!r}")
    _check_backend(backend)
    if axis_name not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis_name!r}: {mesh.shape}")

    spec = PartitionSpec(axis_name)

    # One jitted program per (shapes, num_iters, offsets, tol) — repeat
    # calls of the returned `run` hit the jit cache instead of re-tracing
    # shard_map.
    @partial(jax.jit, static_argnames=("num_iters", "offsets", "tol",
                                       "return_trace"))
    def _run(g, d, s, p, nbr_idx, nbr_mask, theta0, *, num_iters, offsets,
             tol, return_trace=False):
        j_nodes = d.shape[0]
        k_slots = p.shape[1]

        def node_program(g, d, s, p, nbr_idx, nbr_mask, theta0):
            # Every operand arrives with a leading per-device axis of 1.
            exchange = _make_exchange(mode, axis_name, j_nodes, offsets,
                                      nbr_idx)

            def one_round(theta):
                nbr_theta = exchange(theta)
                if backend in _PALLAS_BACKENDS:
                    from repro.kernels.ops import dekrr_step

                    # local θ table: row 0 = own θ, rows 1…K = neighbors
                    table = jnp.concatenate([theta, nbr_theta], axis=0)
                    local_idx = jnp.arange(
                        1, k_slots + 1, dtype=jnp.int32)[None]
                    return dekrr_step(
                        g, d, s, p, table, local_idx,
                        jnp.zeros((1,), jnp.int32), nbr_mask)
                return _node_step(g[0], d[0], s[0], p[0], theta[0],
                                  nbr_theta, nbr_mask[0])[None]

            if tol == 0.0:
                if return_trace:
                    # Record the LOCAL per-round delta; the network-wide
                    # max is a device-axis reduction outside the
                    # shard_map, so tracing adds no collective.
                    def round_fn(theta, _):
                        new = one_round(theta)
                        return new, jnp.max(jnp.abs(new - theta))

                    theta, res = lax.scan(round_fn, theta0, None,
                                          length=num_iters)
                    rounds = jnp.full((1,), num_iters, jnp.int32)
                    return theta, rounds, res[None]

                def round_fn(theta, _):
                    return one_round(theta), None

                theta, _ = lax.scan(round_fn, theta0, None,
                                    length=num_iters)
                rounds = jnp.full((1,), num_iters, jnp.int32)
                return theta, rounds

            # genuine early exit: the pmax-fused delta makes the trip
            # decision identical on every device, so the per-device
            # while_loops run the same number of rounds and the
            # collectives inside the body stay matched — converged solves
            # stop paying for the rest of the budget (the warm-start
            # common case).
            def cond_fn(carry):
                _, converged, rounds = carry[:3]
                return jnp.logical_not(converged) & (rounds < num_iters)

            def body_fn(carry):
                theta, converged, rounds = carry[:3]
                new = one_round(theta)
                local = jnp.max(jnp.abs(new - theta))
                delta = lax.pmax(local, axis_name)
                state = (new, converged | (delta < tol), rounds + 1)
                if return_trace:
                    # Preallocated [num_iters] buffer in the carry;
                    # frozen rounds after the stop stay 0.
                    state += (carry[3].at[rounds].set(local),)
                return state

            init = (theta0, jnp.asarray(False), jnp.asarray(0, jnp.int32))
            if return_trace:
                init += (jnp.zeros((num_iters,), theta0.dtype),)
            carry = lax.while_loop(cond_fn, body_fn, init)
            theta, rounds = carry[0], jnp.reshape(carry[2], (1,))
            if return_trace:
                return theta, rounds, carry[3][None]
            return theta, rounds

        sharded = shard_map(
            node_program, mesh=mesh,
            in_specs=(spec, spec, spec, spec, spec, spec, spec),
            out_specs=(spec, spec, spec) if return_trace else (spec, spec),
            # The varying-manual-axes check needs a `vma` on every
            # pallas_call output, which the ops wrappers do not declare,
            # and its while_loop rule rejects the tol path's carries (the
            # trace buffer starts invariant and turns device-varying).
            # Every operand and output here is explicitly sharded anyway;
            # lint J005 (`repro.analysis.jaxpr_lint`) proves the
            # collective-gating control replicated where the check is off.
            check_vma=(backend not in _PALLAS_BACKENDS and tol == 0.0),
        )
        return sharded(g, d, s, p, nbr_idx, nbr_mask, theta0)

    def run(packed: PackedProblem, num_iters: int,
            theta0: jax.Array | None = None, *, tol: float = 0.0,
            return_rounds: bool = False, return_trace: bool = False):
        _check_spmd_problem(packed, mesh, axis_name, mode)
        if tol < 0:
            raise ValueError(f"tol must be >= 0, got {tol}")
        if theta0 is None:
            theta0 = jnp.zeros_like(packed.d)
        outs = _run(packed.g, packed.d, packed.s, packed.p,
                    packed.nbr_idx, packed.nbr_mask, theta0,
                    num_iters=int(num_iters),
                    offsets=packed.offsets, tol=float(tol),
                    return_trace=return_trace)
        theta, rounds = outs[0], outs[1]
        out = (theta,)
        if return_rounds:
            out += (jnp.max(rounds),)
        if return_trace:
            # [J, R] per-device local deltas → network-wide series.
            out += (SolveTrace(residuals=jnp.max(outs[2], axis=0)),)
        return out if len(out) > 1 else theta

    return run


# --------------------------------------------------------------------------
# §II-C communication cost model
# --------------------------------------------------------------------------
def comm_bytes_per_round(packed: PackedProblem, mode: str, *,
                         activation_prob: float = 1.0,
                         censor_fraction: float = 0.0,
                         gossip: str = "bernoulli") -> int | float:
    """(Expected) bytes moved across the network per Eq. 19 round.

    Synchronous base cost (``activation_prob=1``, ``censor_fraction=0``,
    ``gossip="bernoulli"`` — the defaults, returned as an exact int):

    ``"ppermute"``:  Σ_j |N_j| · D_max · itemsize — each node receives one
    padded θ vector from each neighbor (the paper's Σ_j |N_j| D_j metric,
    evaluated at the packed width D_max).
    ``"allgather"``: J · (J−1) · D_max · itemsize — each node receives the
    full network state minus its own shard.

    Multi-output packings ship Dy columns per θ exchange, so every
    formula above carries an extra ·Dy factor (`packed.num_outputs`).

    Async gossip (`repro.dist.async_gossip`) scales the base cost to the
    *expected* payload under randomized activation and COKE censoring:

      * ``gossip="bernoulli"``: a node transmits iff it is active
        (probability ``activation_prob``) and uncensored (probability
        ``1 − censor_fraction``; censoring decisions are data-dependent,
        so callers pass the observed or assumed censor rate) — expected
        bytes = p · (1 − c) · base. Monotone non-decreasing in p and
        non-increasing in c (property-tested).
      * ``gossip="edge"``: exactly one edge gossips per round — two
        directed θ deliveries, censored at rate c, independent of p.

    Note the SPMD *simulation* still moves every collective lane each
    round (ppermute/all_gather are dense); this model prices the payload
    a deployment with point-to-point transport would ship.
    """
    if mode not in _MODES:
        raise ValueError(f"mode must be one of {_MODES}, got {mode!r}")
    if not 0.0 < activation_prob <= 1.0:
        raise ValueError(f"activation_prob must be in (0, 1], "
                         f"got {activation_prob}")
    if not 0.0 <= censor_fraction <= 1.0:
        raise ValueError(f"censor_fraction must be in [0, 1], "
                         f"got {censor_fraction}")
    if gossip not in ("bernoulli", "edge"):
        raise ValueError(f"gossip must be 'bernoulli' or 'edge', "
                         f"got {gossip!r}")
    j_nodes = packed.num_nodes
    # multi-output payloads ship Dy columns per θ exchange
    d_max = packed.max_features * packed.num_outputs
    itemsize = np.dtype(packed.d.dtype).itemsize
    if gossip == "edge":
        return 2 * d_max * itemsize * (1.0 - censor_fraction)
    if mode == "ppermute":
        # Static count recorded at packing time — reading it off
        # packed.nbr_mask here would force a device→host sync on a
        # quantity that never changes after packing. The NumPy fallback
        # covers hand-built PackedProblems that skipped pack_problem.
        num_edges_directed = packed.num_edges_directed
        if num_edges_directed is None:
            num_edges_directed = int(
                np.count_nonzero(np.asarray(packed.nbr_mask)))
        base = num_edges_directed * d_max * itemsize
    else:
        base = j_nodes * (j_nodes - 1) * d_max * itemsize
    if activation_prob == 1.0 and censor_fraction == 0.0:
        return base                      # synchronous: exact int contract
    return base * activation_prob * (1.0 - censor_fraction)
