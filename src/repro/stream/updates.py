"""Incremental maintenance of the Eq. 17 auxiliaries under streaming data.

The batch runtime (`repro.dist.pack_problem`) builds the per-node
auxiliaries from ALL data at once — O(D² N) featurize/Gram work plus an
O(D³) inverse per node. When node j ingests a minibatch (X_b, Y_b) of b
samples, only low-rank pieces of the network state actually change, and
this module folds them in exactly:

  * Gram_jj              += Z_b,j Z_b,jᵀ      (node j's map on the batch)
  * Gram(Z_{p,j}), p∈N_j += Z_b,p Z_b,pᵀ      (each neighbor's map on it)
  * d̃_j                  += Z_b,j Y_bᵀ
  * S̃_j                  += (2c_self,j/|N̂_j|) Z_b,j Z_b,jᵀ
  * P̃_{j,p} / P̃_{p,j}    += rank-b cross terms Z_b,j Z_b,pᵀ / Z_b,p Z_b,jᵀ

so each Eq. 17 matrix A_i of the 1 + |N_j| affected nodes moves by a
rank-b symmetric update c·U Uᵀ, and its maintained inverse follows by the
Woodbury identity

    G ← G − (G U) (c⁻¹ I_b + Uᵀ G U)⁻¹ (G U)ᵀ            — O(D² b + b³)

instead of an O(D³) re-inversion. All 1 + |N_j| nodes update in one
vmapped program (`ingest`), gathered/scattered through the packed
[J, D_max, …] layout, so the per-ingest cost is O(deg · D² b) regardless
of J or of the accumulated sample count.

Normalization. Every data-dependent term of Eq. 17 carries a global 1/N
(N = network-wide sample count), which would couple EVERY node's matrix
to every ingest. The state therefore lives in *unnormalized* space, where
all coefficients are N-free:

    B_j = u_self,j Gram_jj + Σ_{p∈N_j} u_cross,p Gram(Z_{j,p})
    u_self,j  = 1 + (2 c_self,j + |N_j| c_nei,j) / |N̂_j|
    u_cross,j = c_nei,j / |N̂_j|

and `to_packed` re-applies the live 1/N when materializing a
`PackedProblem` (a pure elementwise rescale — the Eq. 19 round map is
invariant to it). The one term that is NOT a rescale is the ridge: the
paper's (λ/J) I sits outside the 1/N, so in unnormalized space it is
ν I with ν = λ N/J. A change of N shifts ν I — a full-rank perturbation
no low-rank update can track — so the stream pins ν at construction
(ν = λ n_ref / J, n_ref = the sample count at stream start). That is the
standard online-ridge convention (fixed absolute regularizer; per-sample
regularization decays as data accumulates), and it is exactly
reproducible from scratch: the stream state after any ingest sequence
equals `pack_problem` on the accumulated data with
λ_eff = λ · n_ref / n_live (`reference_lam`), at rtol 1e-9 under x64
(tests/test_stream.py; keep λ large enough that cond(A) ≲ 1e6 — Woodbury
and direct inversion agree to ~cond·eps).

A per-node DDRF feature *refresh* (new frequencies, possibly a new D_j)
is the one event that is not low-rank: every term involving the node's
feature map changes basis. `refresh_node` rebuilds exactly that node's
slot — its B_j/inverse/d̃_j/S̃_j/P̃_j row and the P̃_{p,·} slots of its
neighbors that couple against it — from the accumulated raw data, leaves
every other node's inverse untouched (their B_p do not involve fm_j),
and re-pads the packed layout when max(node_dims) changes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rff import FeatureMap
from repro.dist.dekrr_spmd import (PackedProblem, _featurize_raw,
                                   _gauss_jordan_inv, _stage_feature_maps,
                                   pack_problem)

__all__ = [
    "StreamAux",
    "init_stream_aux",
    "ingest",
    "refresh_node",
    "to_packed",
    "repad_theta",
    "reference_lam",
]


# --------------------------------------------------------------------------
# State container
# --------------------------------------------------------------------------
@dataclasses.dataclass
class StreamAux:
    """Streaming sufficient statistics in the packed [J, D_max, …] layout.

    Array state (jax arrays; unnormalized space — see module docstring):
      binv: [J, D_max, D_max]    (B_j + ν I)⁻¹, Woodbury-maintained; the
                                 padded diagonal block is the identity
                                 (masked off at materialization).
      zy:   [J, D_max]           d̃_j = Z_jj Y_jᵀ (multi-output streams
                                 carry [J, D_max, Dy] — one label column
                                 per output; every other auxiliary is
                                 features-only and keeps its shape).
      st:   [J, D_max, D_max]    S̃_j.
      pt:   [J, K, D_max, D_max] P̃_{j, nbr_idx[j,k]}.
      theta_mask / nbr_idx / nbr_mask: the packed layout tables
                                 (`repro.dist.PackedProblem` semantics).

    Staged feature maps (what lets ANY node featurize a minibatch in one
    uniform padded program): omega [J, F_max, dim], bias [J, F_max],
    feat_idx [J, D_max], scale [J] — staged by
    `repro.dist._stage_feature_maps`, which shares its padding
    (`_pad_maps`) and its tables (`_feature_layout`) with the pack.

    Scalars / metadata: n_live (accumulated network sample count — the
    1/N used at materialization), nu (the pinned absolute ridge λ·n_ref/J),
    n_ref, node_dims, offsets, kind, and the N-free coupling coefficients
    u_self/u_cross/u_s [J] (host-side numpy — read per ingest without a
    device sync) plus two host-side slot-table derivatives that keep the
    per-minibatch hot path free of device→host transfers:
    ingest_tables = (idx [J, 1+K], gate [J, 1+K], cvec [J, 1+K]) — the
    affected-row indices, live-slot gates, and Woodbury coefficients of
    each node's ingest — and the reverse slot table rslot [J, K]
    (rslot[j, k] = the slot of node j inside nbr_idx[j, k]'s table).
    """

    binv: jax.Array
    zy: jax.Array
    st: jax.Array
    pt: jax.Array
    theta_mask: jax.Array
    nbr_idx: jax.Array
    nbr_mask: jax.Array
    omega: jax.Array
    bias: jax.Array
    feat_idx: jax.Array
    scale: jax.Array
    u_self: np.ndarray
    u_cross: np.ndarray
    u_s: np.ndarray
    ingest_tables: tuple
    rslot: np.ndarray
    n_live: int
    nu: float
    n_ref: int
    node_dims: tuple[int, ...]
    offsets: tuple[int, ...] | None
    kind: str

    @property
    def num_nodes(self) -> int:
        return int(self.zy.shape[0])

    @property
    def max_features(self) -> int:
        return int(self.zy.shape[1])

    @property
    def num_slots(self) -> int:
        return int(self.nbr_idx.shape[1])


def reference_lam(aux: StreamAux) -> float:
    """The ridge a from-scratch `DeKRRSolver` on the accumulated data must
    use to reproduce this stream state exactly: λ_eff = ν·J/N_live
    (= λ·n_ref/n_live — the pinned absolute ridge re-expressed at the live
    normalization)."""
    return aux.nu * aux.num_nodes / aux.n_live


# --------------------------------------------------------------------------
# Layout helpers (feature-map padding and tables are pack_problem's own —
# repro.dist._pad_maps and _feature_layout — and
# tests/test_obs.py::test_device_staged_inputs_equal_numpy_staging pins
# both stagings to a numpy padding)
# --------------------------------------------------------------------------
def _ingest_tables(nbr_idx: np.ndarray, nbr_mask: np.ndarray,
                   u_self: np.ndarray, u_cross: np.ndarray,
                   dtype) -> tuple:
    """Host-side per-node (idx, gate, cvec) rows for `ingest` — constant
    between refreshes, precomputed so the hot path never touches device
    arrays or allocates."""
    j_nodes, k_slots = nbr_idx.shape
    idx = np.concatenate(
        [np.arange(j_nodes, dtype=np.int32)[:, None],
         nbr_idx.astype(np.int32)], axis=1)                 # [J, 1+K]
    gate = np.concatenate(
        [np.ones((j_nodes, 1)), (nbr_mask != 0).astype(np.float64)],
        axis=1).astype(dtype)
    cvec = np.concatenate(
        [u_self[:, None],
         np.broadcast_to(u_cross[:, None], (j_nodes, k_slots))],
        axis=1).astype(dtype)
    return idx, gate, cvec


def _reverse_slots(nbr_idx: np.ndarray, nbr_mask: np.ndarray) -> np.ndarray:
    """rslot[j, k] = slot index of node j inside node nbr_idx[j, k]'s
    table (0 on masked slots — their updates are exact zeros anyway)."""
    j_nodes, k_slots = nbr_idx.shape
    rslot = np.zeros((j_nodes, k_slots), dtype=np.int32)
    for j in range(j_nodes):
        for k in range(k_slots):
            if not nbr_mask[j, k]:
                continue
            p = int(nbr_idx[j, k])
            (hits,) = np.nonzero((np.asarray(nbr_idx[p]) == j)
                                 & (np.asarray(nbr_mask[p]) != 0))
            rslot[j, k] = int(hits[0])
    return rslot


def init_stream_aux(solver, packed: PackedProblem | None = None
                    ) -> StreamAux:
    """Seed the streaming state from a `DeKRRSolver` snapshot.

    Uses (or builds) the batched `pack_problem` of the solver and converts
    it to unnormalized space: binv = g/N (+ identity padding — exact, the
    packed g IS N·(B + νI)⁻¹ on live coordinates), d̃ = d·N, S̃ = s·N,
    P̃ = p·N. Pins the ridge at ν = λ·N/J (see module docstring).
    """
    if getattr(solver, "_gram_fn", None) is not None:
        raise ValueError("repro.stream cannot maintain auxiliaries built "
                         "through a custom gram_fn")
    if any(getattr(nd, "bags", None) is not None for nd in solver.data):
        raise ValueError(
            "repro.stream cannot maintain auxiliaries for "
            "aggregate-observation (bagged) nodes — a bag couples its "
            "members through the label term, so a minibatch fold is not "
            "rank-b in the bagged Gram")
    if packed is None:
        packed = pack_problem(solver)
    dtype = np.asarray(packed.d).dtype
    n = solver.N
    staged = _stage_feature_maps(solver.feature_maps, dtype)
    if staged["node_dims"] != packed.node_dims:
        raise ValueError("solver feature maps disagree with packed.node_dims")

    mask = packed.theta_mask
    pad_eye = jnp.eye(packed.max_features, dtype=dtype)[None] \
        * (1.0 - mask)[:, :, None] * (1.0 - mask)[:, None, :]
    binv = packed.g / n + pad_eye

    hood = solver.topology.degrees.astype(np.float64) + 1.0
    c_nei = np.asarray(solver.c_nei, np.float64)
    c_self = np.asarray(solver.c_self, np.float64)
    degs = solver.topology.degrees.astype(np.float64)
    u_self = 1.0 + (2.0 * c_self + degs * c_nei) / hood
    u_cross = c_nei / hood
    u_s = 2.0 * c_self / hood

    nbr_idx = np.asarray(packed.nbr_idx)
    nbr_mask = np.asarray(packed.nbr_mask)
    u_self = u_self.astype(dtype)
    u_cross = u_cross.astype(dtype)
    return StreamAux(
        binv=binv, zy=packed.d * n, st=packed.s * n, pt=packed.p * n,
        theta_mask=mask, nbr_idx=packed.nbr_idx, nbr_mask=packed.nbr_mask,
        omega=jnp.asarray(staged["omega"]), bias=jnp.asarray(staged["bias"]),
        feat_idx=jnp.asarray(staged["feat_idx"]),
        scale=jnp.asarray(staged["scale"].astype(dtype)),
        u_self=u_self, u_cross=u_cross, u_s=u_s.astype(dtype),
        ingest_tables=_ingest_tables(nbr_idx, nbr_mask, u_self, u_cross,
                                     dtype),
        rslot=_reverse_slots(nbr_idx, nbr_mask),
        n_live=int(n), nu=float(solver.config.lam * n / solver.J),
        n_ref=int(n), node_dims=packed.node_dims, offsets=packed.offsets,
        kind=staged["kind"],
    )


# --------------------------------------------------------------------------
# Rank-b Woodbury ingest — one vmapped program over the affected nodes
# --------------------------------------------------------------------------
def _packed_featurize(omega, bias, feat_idx, feat_mask, scale, x, col_mask,
                      kind):
    """One node's map on a minibatch, in packed feature space: [D_max, B].
    Identical arithmetic to `repro.dist._node_aux`'s featurize+pack
    (HIGHEST-precision einsum, take/scale/mask) so parity with the batch
    build holds at rtol 1e-9."""
    raw = _featurize_raw(omega, bias, x, kind)
    return (jnp.take(raw, feat_idx, axis=0) * scale * feat_mask[:, None]
            * col_mask[None, :])


@partial(jax.jit, static_argnames=("kind",))
def _ingest_kernel(binv, zy, st, pt, theta_mask, omega, bias, feat_idx,
                   scale, idx, gate, cvec, rslot_j, u_s_j, u_cross_j,
                   xb, yb, col_mask, *, kind):
    """Fold one minibatch at node idx[0] into (binv, zy, st, pt).

    idx [1+K]: the affected rows (the node, then its slot table); gate
    [1+K]: 1.0 for the node and live slots, 0.0 for padded slots (their
    contributions vanish exactly); cvec [1+K]: the rank-b coefficients
    (u_self of the node, then its u_cross for every neighbor row).
    """
    hi = jax.lax.Precision.HIGHEST
    feat_mask = theta_mask[idx]                        # [A, D_max]

    def feat(om, bi, fi, fm, sc):
        return _packed_featurize(om, bi, fi, fm, sc, xb, col_mask, kind)

    zb = jax.vmap(feat)(omega[idx], bias[idx], feat_idx[idx], feat_mask,
                        scale[idx])                    # [A, D_max, B]
    zb = zb * gate[:, None, None]

    # Woodbury: G += -(G U)(c⁻¹I + Uᵀ G U)⁻¹(G U)ᵀ per affected node
    g_rows = binv[idx]                                 # [A, D, D]
    gu = jnp.einsum("aij,ajb->aib", g_rows, zb, precision=hi)
    utgu = jnp.einsum("aib,aic->abc", zb, gu, precision=hi)
    safe_c = jnp.where(cvec != 0, cvec, 1.0)
    mid = (jnp.eye(zb.shape[-1], dtype=zb.dtype)[None]
           / safe_c[:, None, None] + utgu)
    sol = jnp.linalg.solve(mid, jnp.swapaxes(gu, 1, 2))  # [A, B, D]
    corr = -jnp.einsum("aib,abj->aij", gu, sol, precision=hi)
    corr = corr * (cvec != 0)[:, None, None]
    binv = binv.at[idx].add(corr)

    zbj, zbn = zb[0], zb[1:]
    if zy.ndim == 3:                         # multi-output: yb is [B, Dy]
        zy = zy.at[idx[0]].add(
            jnp.einsum("db,bo->do", zbj, yb, precision=hi))
    else:
        zy = zy.at[idx[0]].add(
            jnp.einsum("db,b->d", zbj, yb, precision=hi))
    gram_b = jnp.einsum("ab,cb->ac", zbj, zbj, precision=hi)
    st = st.at[idx[0]].add(u_s_j * gram_b)
    # P̃_{j,k} += u_cross[j]·Z_b,j Z_b,pᵀ ; P̃_{p,rslot} += u_cross[j]·Z_b,p Z_b,jᵀ
    pt = pt.at[idx[0]].add(
        u_cross_j * jnp.einsum("db,kcb->kdc", zbj, zbn, precision=hi))
    pt = pt.at[idx[1:], rslot_j].add(
        u_cross_j * jnp.einsum("kdb,cb->kdc", zbn, zbj, precision=hi))
    return binv, zy, st, pt


def _bucket(b: int) -> int:
    """Pad minibatches to power-of-two buckets (min 8) so the jitted
    ingest program compiles once per bucket, not once per batch size."""
    return max(8, 1 << (b - 1).bit_length())


def ingest(aux: StreamAux, node: int, xb, yb) -> StreamAux:
    """Fold minibatch (xb [d, b], yb [b] — or [b, Dy] when the stream
    state carries a multi-output `zy` [J, D_max, Dy]) arriving at `node`
    into the stream state — O(deg · D² b) exact rank-b updates, no O(D³)
    work. Returns a new `StreamAux` (the array state is functional)."""
    j = int(node)
    if not 0 <= j < aux.num_nodes:
        raise ValueError(f"node {j} out of range for J={aux.num_nodes}")
    dtype = aux.zy.dtype
    xb = np.asarray(xb, dtype=dtype)
    if aux.zy.ndim == 3:
        dy = aux.zy.shape[2]
        yb = np.asarray(yb, dtype=dtype)
        if yb.ndim != 2 or yb.shape[1] != dy:
            raise ValueError(f"multi-output stream (Dy={dy}) needs "
                             f"y [b, {dy}]; got {yb.shape}")
    else:
        yb = np.asarray(yb, dtype=dtype).reshape(-1)
    if xb.ndim != 2 or xb.shape[1] != yb.shape[0]:
        raise ValueError(f"minibatch must be x [d, b], y [b]; got "
                         f"{xb.shape} / {yb.shape}")
    b = xb.shape[1]
    if b == 0:
        return aux
    bb = _bucket(b)
    col_mask = (np.arange(bb) < b).astype(dtype)
    xb = np.pad(xb, ((0, 0), (0, bb - b)))
    yb = np.pad(yb, ((0, bb - b),) + ((0, 0),) * (yb.ndim - 1))

    idx_t, gate_t, cvec_t = aux.ingest_tables      # host-side, no syncs

    binv, zy, st, pt = _ingest_kernel(
        aux.binv, aux.zy, aux.st, aux.pt, aux.theta_mask, aux.omega,
        aux.bias, aux.feat_idx, aux.scale,
        jnp.asarray(idx_t[j]), jnp.asarray(gate_t[j]),
        jnp.asarray(cvec_t[j]), jnp.asarray(aux.rslot[j]),
        aux.u_s[j], aux.u_cross[j],
        jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(col_mask),
        kind=aux.kind)
    return dataclasses.replace(aux, binv=binv, zy=zy, st=st, pt=pt,
                               n_live=aux.n_live + b)


# --------------------------------------------------------------------------
# Per-node feature refresh (DDRF re-selection after drift)
# --------------------------------------------------------------------------
def _resize_packed(arr, old_d, new_d, matrix_axes):
    """Grow or shrink trailing feature axes of a packed array. Shrinking
    is only legal when no live coordinate lives beyond new_d (true by
    construction: new_d = max(new node_dims))."""
    if new_d == old_d:
        return arr
    arr = np.asarray(arr)
    if new_d > old_d:
        widths = [(0, 0)] * arr.ndim
        for ax in matrix_axes:
            widths[ax] = (0, new_d - old_d)
        return np.pad(arr, widths)
    slicer = [slice(None)] * arr.ndim
    for ax in matrix_axes:
        slicer[ax] = slice(0, new_d)
    return arr[tuple(slicer)]


def refresh_node(aux: StreamAux, node: int, new_fmap: FeatureMap,
                 feature_maps: Sequence[FeatureMap],
                 data_x: Sequence, data_y) -> StreamAux:
    """Rebuild node `node`'s slot after a DDRF feature refresh.

    `feature_maps` is the post-refresh list (entry `node` == `new_fmap`);
    `data_x[i]` is node i's ACCUMULATED inputs [d, N_i] — only the node
    and its live neighbors are read, other entries may be None; `data_y`
    the node's accumulated labels.

    Only state involving the refreshed map is recomputed: the node's
    B/inverse/d̃/S̃/P̃ row and the neighbors' P̃ slots that couple against
    it. Neighbor B_p matrices do not involve fm_node (their cross terms
    are fm_p on X_node, and X_node is unchanged), so every other inverse
    is left bit-identical. When max(node_dims) changes the whole layout
    re-pads; carry per-node θ across with `repad_theta`.
    """
    j = int(node)
    dtype = aux.zy.dtype
    if feature_maps[j] is not new_fmap:
        raise ValueError(
            "feature_maps[node] must be the refreshed map itself — the "
            "slot is rebuilt from feature_maps, so a stale entry would "
            "silently rebuild with the OLD map")
    staged = _stage_feature_maps(feature_maps, dtype)
    new_dims = staged["node_dims"]
    if new_dims[:j] + new_dims[j + 1:] != \
            aux.node_dims[:j] + aux.node_dims[j + 1:]:
        raise ValueError("refresh_node may only change the refreshed "
                         "node's feature count")
    old_d = aux.max_features
    new_d = max(new_dims)
    hi = jax.lax.Precision.HIGHEST

    # Re-pad the packed arrays to the new D_max (identity padding of binv
    # is restored for the grown region; shrinking only ever cuts padding).
    binv = np.array(_resize_packed(aux.binv, old_d, new_d, (1, 2)))
    if new_d > old_d:
        for i in range(new_d - old_d):
            binv[:, old_d + i, old_d + i] = 1.0
    zy = np.array(_resize_packed(aux.zy, old_d, new_d, (1,)))
    st = np.array(_resize_packed(aux.st, old_d, new_d, (1, 2)))
    pt = np.array(_resize_packed(aux.pt, old_d, new_d, (2, 3)))
    theta_mask = (np.arange(new_d)[None, :]
                  < np.asarray(new_dims)[:, None]).astype(dtype)

    omega = jnp.asarray(staged["omega"])
    bias = jnp.asarray(staged["bias"])
    feat_idx = jnp.asarray(staged["feat_idx"])
    scale = jnp.asarray(staged["scale"].astype(dtype))
    fmask = jnp.asarray(theta_mask)

    def feats(i: int, x) -> jax.Array:
        x = jnp.asarray(np.asarray(x, dtype=dtype))
        ones = jnp.ones((x.shape[1],), dtype)
        return _packed_featurize(omega[i], bias[i], feat_idx[i], fmask[i],
                                 scale[i], x, ones, aux.kind)

    if aux.zy.ndim == 3:                    # multi-output: y_j is [N, Dy]
        y_j = jnp.asarray(np.asarray(data_y, dtype=dtype)
                          .reshape(-1, aux.zy.shape[2]))
    else:
        y_j = jnp.asarray(np.asarray(data_y, dtype=dtype).reshape(-1))
    z_self = feats(j, data_x[j])                       # [D', N_j]
    u_self = aux.u_self[j]
    u_cross = aux.u_cross
    gram_self = jnp.einsum("an,bn->ab", z_self, z_self, precision=hi)

    b_new = u_self * gram_self
    if y_j.ndim == 2:
        zy_new = jnp.einsum("dn,no->do", z_self, y_j, precision=hi)
    else:
        zy_new = jnp.einsum("dn,n->d", z_self, y_j, precision=hi)
    st_new = aux.u_s[j] * gram_self

    nbr_row = np.asarray(aux.nbr_idx[j])
    nbr_mask_row = np.asarray(aux.nbr_mask[j])
    pt_j = np.zeros((aux.num_slots, new_d, new_d), dtype=dtype)
    for k in range(aux.num_slots):
        if not nbr_mask_row[k]:
            continue
        p = int(nbr_row[k])
        z_jp = feats(j, data_x[p])                     # fm_new on X_p
        z_pj = feats(p, data_x[j])                     # fm_p on X_j
        z_pp = feats(p, data_x[p])                     # fm_p on X_p
        b_new = b_new + u_cross[p] * jnp.einsum(
            "an,bn->ab", z_jp, z_jp, precision=hi)
        pt_j[k] = np.asarray(
            u_cross[j] * jnp.einsum("an,bn->ab", z_self, z_pj,
                                    precision=hi)
            + u_cross[p] * jnp.einsum("an,bn->ab", z_jp, z_pp,
                                      precision=hi))
        pt[p, aux.rslot[j, k]] = np.asarray(
            u_cross[p] * jnp.einsum("an,bn->ab", z_pp, z_jp, precision=hi)
            + u_cross[j] * jnp.einsum("an,bn->ab", z_pj, z_self,
                                      precision=hi))
    pt[j] = pt_j

    mj = fmask[j]
    a_unnorm = (b_new + aux.nu * jnp.diag(mj)
                + jnp.diag(1.0 - mj))
    binv_j = _gauss_jordan_inv(a_unnorm)
    binv[j] = np.asarray(binv_j * mj[:, None] * mj[None, :]
                         + jnp.diag(1.0 - mj))
    zy[j] = np.asarray(zy_new)
    st[j] = np.asarray(st_new)

    return dataclasses.replace(
        aux,
        binv=jnp.asarray(binv), zy=jnp.asarray(zy),
        st=jnp.asarray(st), pt=jnp.asarray(pt),
        theta_mask=jnp.asarray(theta_mask),
        omega=omega, bias=bias, feat_idx=feat_idx, scale=scale,
        node_dims=new_dims,
    )


# --------------------------------------------------------------------------
# Materialization + θ carry
# --------------------------------------------------------------------------
@jax.jit
def _materialize(binv, zy, st, pt, mask, n):
    fouter = mask[:, :, None] * mask[:, None, :]
    return binv * fouter * n, zy / n, st / n, pt / n


def to_packed(aux: StreamAux) -> PackedProblem:
    """Materialize the live `PackedProblem` at the current normalization —
    a pure elementwise rescale (no inverses, no featurization). The result
    equals `pack_problem` on the accumulated data with
    λ_eff = `reference_lam(aux)` at rtol 1e-9 under x64, and plugs into
    every solver the packed runtime offers (`solve_batched`,
    `async_solve_batched`, the SPMD runners, `repro.core.acceleration`).
    """
    n = jnp.asarray(float(aux.n_live), aux.zy.dtype)
    g, d, s, p = _materialize(aux.binv, aux.zy, aux.st, aux.pt,
                              aux.theta_mask, n)
    num_edges = int(np.count_nonzero(np.asarray(aux.nbr_mask)))
    return PackedProblem(g=g, d=d, s=s, p=p, theta_mask=aux.theta_mask,
                         nbr_idx=aux.nbr_idx, nbr_mask=aux.nbr_mask,
                         offsets=aux.offsets, node_dims=aux.node_dims,
                         num_edges_directed=num_edges)


def repad_theta(theta, old_dims: Sequence[int], new_dims: Sequence[int],
                *, reset: Sequence[int] = ()) -> jax.Array:
    """Carry a packed θ across a node_dims change (feature refresh).

    Rows in `reset` (the refreshed nodes — their θ lives in the OLD
    feature basis) restart from zero; every other row re-pads into the
    new [J, max(new_dims)] layout (multi-output θ [J, max(old_dims), Dy]
    keeps its trailing Dy axis). A non-reset row whose D_j shrank is a
    stale iterate and raises — truncating it would silently drop live
    coordinates.
    """
    old_dims = tuple(int(v) for v in old_dims)
    new_dims = tuple(int(v) for v in new_dims)
    if len(old_dims) != len(new_dims):
        raise ValueError("node count cannot change across a refresh")
    theta = np.asarray(theta)
    lead = (len(old_dims), max(old_dims))
    if theta.shape[:2] != lead or theta.ndim not in (2, 3):
        raise ValueError(
            f"theta has shape {theta.shape} but old_dims describe "
            f"{lead} (+ an optional trailing Dy axis) — pass the θ that "
            f"belongs to the OLD packing")
    reset = {int(r) for r in reset}
    out = np.zeros((len(new_dims), max(new_dims)) + theta.shape[2:],
                   dtype=theta.dtype)
    for i, (do, dn) in enumerate(zip(old_dims, new_dims)):
        if i in reset:
            continue
        if do > dn:
            raise ValueError(
                f"node {i} shrank from D_j={do} to {dn} but is not in "
                f"reset — its θ is stale against the refreshed basis")
        out[i, :do] = theta[i, :do]
    return jnp.asarray(out)
