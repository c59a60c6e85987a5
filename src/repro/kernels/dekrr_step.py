"""Fused packed DeKRR round (Eq. 19) for all J nodes — Pallas TPU kernel.

One Eq. 19 round on the packed problem is, per node j,

    θ_j ← G_j (d_j + S_j θ_j + Σ_k m_{j,k} P_{j,k} θ_{nbr(j,k)})

with G/S [D, D], P [K, D, D] blocks padded to the network maximum D = D_max.
The XLA path (`repro.dist.step_batched`) expresses this as a gather plus a
vmapped chain of batched GEMMs; XLA materializes the gathered [J, K, D]
neighbor-θ tensor and the [J, D] intermediates in HBM between them. This
kernel fuses the whole round so that per grid step only node j's blocks move
HBM→VMEM and θ never leaves VMEM:

    grid = (J,)  — one program per node, blocks streamed by BlockSpec:
      θ table   [T, D]        VMEM-resident across the whole grid (the
                              reduction operand; T = J in batched mode)
      G_j, S_j  [1, D, D]     streamed per step
      P_j       [1, K, D, D]  streamed per step
      d_j       [Dy, D]       streamed per step (block of d [J, Dy, D])
    per step j: acc  = d_j + S_j θ_{self(j)}            (MXU)
                acc += Σ_k m_{j,k} · P_{j,k} θ_{row(j,k)}   (MXU, K unrolled)
                out_j = G_j acc                         (MXU)

The neighbor gather is done *inside* the kernel with the slot table: the
int32 tables `nbr_idx` [J, K] / `self_idx` [J] arrive via scalar prefetch
(`PrefetchScalarGridSpec`, SMEM) and index dynamic [1, D] row reads of the
VMEM θ table — no one-hot matmul, no gathered [J, K, D] tensor in HBM.

TPU tiling. Mosaic takes a block whose last two dims are multiples of
(8, 128) or equal to the array's. Per-node row blocks therefore ride a
leading node axis — d and the output are [J, Dy, D] with block
(None, Dy, D) — and the resident θ table is read one row at a time: a
dynamic sublane offset is accepted for a 1-row slice, but a Dy-row slice
at t·Dy is not provably 8-aligned (`_rows` / `_set_rows`).

Decoupling the θ-table row from the node id (`self_idx`) lets the SPMD
per-device node program reuse the identical kernel: a device holding one
node calls it with J = 1, the table [1 + K, D] = [own θ; received neighbor
θs], self_idx = [0] and nbr_idx = [[1 … K]] (see
`repro.dist.make_spmd_solver(backend="pallas")`).

Padding contract (same closure argument as `repro.dist.pack_problem`): rows
i ≥ D_j of G_j are zero, so padded coordinates of the output are *exact*
zeros; masked slots carry zero P blocks, so the `nbr_mask` multiply is
belt-and-braces. Vectors are kept as [1, D] rows and every product is a
dot_general contracting the matrix's second axis (computing (M v)ᵀ without
materializing any transpose).

Multi-output targets (Dy > 1) keep the same kernel: the θ table arrives
*flattened* along the sublane axis as [T·Dy, D], with table row t owning
the Dy consecutive rows [t·Dy, (t+1)·Dy) (θᵀ for that node, laid out
[Dy, D]); d and the output carry the [J, Dy, D] node axis. The kernel
derives Dy from the d block's sublane extent and scales every dynamic row
read by it. A [Dy, D] row block through the same dot_generals is exactly
the per-output loop batched on the free axis — no arithmetic changes.

VMEM working set per step: T·D (θ, Dy folded into T) + (2 + K)·D²
(G, S, P) + 3·D·Dy (d, acc, out) floats — for the paper's D ≤ 512, K = 4
at f32 that is ~6.3 MB, within the 16 MB/core budget. This formula is executable as
`repro.analysis.vmem.estimate_dekrr_step` (the consolidated table for all
four kernels lives in that module's docstring); the `ops.dekrr_step`
wrapper checks it before dispatch and raises `VmemBudgetError` on
over-budget shapes. All dims must be padded by the wrapper: D to lane
multiples of 128, the θ table to sublane multiples of 8.

The async-gossip runtime (`repro.dist.async_gossip`) uses the
activation-masked variant (`active=` on `dekrr_step_pallas`): a fourth
scalar-prefetch vector gates each grid step, and inactive nodes copy their
θ row through instead of running the MXU chain — with `active` all-ones
the masked kernel is bit-for-bit the synchronous one (shared
`_eq19_update` body).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (M v)ᵀ as a row vector: contract [1, D] with [D', D] over the second axis.
_ROW_TIMES_MAT_T = (((1,), (1,)), ((), ()))


def _row_times(rows, mat):
    """rows [Dy, D] · mat [D', D]ᵀ → [Dy, D'] == (mat @ rows.T).T"""
    return jax.lax.dot_general(
        rows, mat, _ROW_TIMES_MAT_T,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=rows.dtype)


def _rows(ref, start, n: int):
    """Rows [start, start + n) of a 2-D VMEM ref as an [n, D] value, one
    dynamic single-row load each (see the module docstring)."""
    if n == 1:
        return ref[pl.ds(start, 1), :]
    return jnp.concatenate([ref[pl.ds(start + o, 1), :] for o in range(n)],
                           axis=0)


def _set_rows(ref, start, value) -> None:
    """Store an [n, D] value at rows [start, start + n) of a 2-D VMEM ref,
    one dynamic single-row store per row."""
    for o in range(value.shape[0]):
        ref[pl.ds(start + o, 1), :] = value[o:o + 1]


def _set_lane(ref, lane, value) -> None:
    """ref[0, lane] = value on a [1, W] VMEM block — a resident per-round
    row that each node's grid step fills in turn (a (1, 1) block of an
    [R, J] array is not a legal TPU block shape)."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 1)
    ref[...] = jnp.where(lanes == lane, value.astype(ref.dtype), ref[...])


def _eq19_update(j, theta_self, nbr_rows, nbr_mask_ref, g_ref, d_ref, s_ref,
                 p_ref):
    """Node j's Eq. 19 update as a [Dy, D] row block — the one arithmetic
    body every round kernel runs (sync, activation-masked, fused solve,
    async chain, Chebyshev), so they can never drift apart.

    theta_self [Dy, D] is the node's own θ; ``nbr_rows(k)`` returns slot
    k's [Dy, D] neighbor θ (from the θ table, or from the async staleness
    buffers)."""
    dtype = theta_self.dtype
    acc = d_ref[...] + _row_times(theta_self, s_ref[0])      # d + S θ
    for k in range(nbr_mask_ref.shape[1]):                   # K static unroll
        mask_k = nbr_mask_ref[j, k].astype(dtype)
        acc += _row_times(nbr_rows(k), p_ref[0, k]) * mask_k  # Σ m P θ_nbr
    return _row_times(acc, g_ref[0])                          # G (…)


def _table_update(j, nbr_idx_ref, self_idx_ref, nbr_mask_ref, table_ref,
                  g_ref, d_ref, s_ref, p_ref):
    """`_eq19_update` with self and neighbor θ read from a θ table through
    the slot tables; returns (θ_self, new), both [Dy, D]."""
    dy = d_ref.shape[0]
    theta_self = _rows(table_ref, self_idx_ref[j] * dy, dy)
    new = _eq19_update(
        j, theta_self, lambda k: _rows(table_ref, nbr_idx_ref[j, k] * dy, dy),
        nbr_mask_ref, g_ref, d_ref, s_ref, p_ref)
    return theta_self, new


def _dekrr_step_kernel(nbr_idx_ref, self_idx_ref, nbr_mask_ref,
                       theta_ref, g_ref, d_ref, s_ref, p_ref, out_ref):
    """One node's Eq. 19 update; grid position = node id.

    Scalar prefetch (SMEM): nbr_idx [J, K] int32, self_idx [J] int32,
    nbr_mask [J, K] int32. Tensor operands: theta [T·Dy, D] (full table,
    VMEM-resident), g/s [1, D, D], d [Dy, D], p [1, K, D, D]; out [Dy, D].
    """
    j = pl.program_id(0)
    out_ref[...] = _table_update(j, nbr_idx_ref, self_idx_ref, nbr_mask_ref,
                                 theta_ref, g_ref, d_ref, s_ref, p_ref)[1]


def _dekrr_step_masked_kernel(nbr_idx_ref, self_idx_ref, nbr_mask_ref,
                              active_ref, theta_ref, g_ref, d_ref, s_ref,
                              p_ref, out_ref):
    """Activation-masked Eq. 19 round (async gossip): grid position = node
    id; nodes with active[j] == 0 pass their θ row through untouched —
    the G/S/P block streams still flow (the Pallas pipeline's index maps
    are activation-oblivious) but no MXU work runs and no update lands.

    Scalar prefetch adds active [J] int32 after the shared slot tables.
    With active all-ones this is bit-for-bit `_dekrr_step_kernel` (same
    `_eq19_update` body).
    """
    j = pl.program_id(0)
    is_active = active_ref[j] != 0

    @pl.when(is_active)
    def _update():
        out_ref[...] = _table_update(j, nbr_idx_ref, self_idx_ref,
                                     nbr_mask_ref, theta_ref, g_ref, d_ref,
                                     s_ref, p_ref)[1]

    @pl.when(jnp.logical_not(is_active))
    def _passthrough():
        dy = d_ref.shape[0]
        out_ref[...] = _rows(theta_ref, self_idx_ref[j] * dy, dy)


def dekrr_step_pallas(g: jax.Array, d: jax.Array, s: jax.Array,
                      p: jax.Array, theta: jax.Array, nbr_idx: jax.Array,
                      self_idx: jax.Array, nbr_mask: jax.Array, *,
                      active: jax.Array | None = None,
                      interpret: bool = False) -> jax.Array:
    """Raw pallas_call. All dims must already be padded/aligned:

      g/s [J, D, D], d [J, Dy, D], p [J, K, D, D] with K ≥ 1 and D a
      multiple of 128; theta [T·Dy, D] with T·Dy padded to a multiple of
      8; nbr_idx [J, K] int32 *table* rows (pre-flattening — the kernel
      scales by Dy); self_idx [J] int32; nbr_mask [J, K] int32.
    ``active`` ([J] int32, optional) selects the activation-masked async
    kernel: nodes with active[j] == 0 emit their own θ rows unchanged.
    Returns the post-round θ rows, [J, Dy, D] (callers with T ≠ J
    re-assemble their table themselves).
    """
    j_nodes, dy, d_feat = d.shape
    k_slots = p.shape[1]
    t_rows = theta.shape[0]
    assert d_feat % 128 == 0 and t_rows % 8 == 0, (d_feat, t_rows)
    assert k_slots >= 1, "pad the slot axis to K >= 1 (zero P blocks)"

    scalar_args = (nbr_idx, self_idx, nbr_mask)
    kernel = _dekrr_step_kernel
    if active is not None:
        scalar_args = scalar_args + (active,)
        kernel = _dekrr_step_masked_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalar_args),
        grid=(j_nodes,),
        in_specs=[
            pl.BlockSpec((t_rows, d_feat), lambda j, *_: (0, 0)),   # θ table
            pl.BlockSpec((1, d_feat, d_feat), lambda j, *_: (j, 0, 0)),
            pl.BlockSpec((None, dy, d_feat), lambda j, *_: (j, 0, 0)),
            pl.BlockSpec((1, d_feat, d_feat), lambda j, *_: (j, 0, 0)),
            pl.BlockSpec((1, k_slots, d_feat, d_feat),
                         lambda j, *_: (j, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, dy, d_feat), lambda j, *_: (j, 0, 0)),
    )
    flops_per_node = 2 * (2 + k_slots) * d_feat * d_feat * dy
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((j_nodes, dy, d_feat), theta.dtype),
        cost_estimate=pl.CostEstimate(
            flops=j_nodes * flops_per_node,
            bytes_accessed=(t_rows * d_feat
                            + j_nodes * (3 + k_slots) * d_feat * d_feat
                            ) * theta.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(*scalar_args, theta, g, d, s, p)


def _table_rows(table: jax.Array, idx: jax.Array, dy: int) -> jax.Array:
    """Gather the dy consecutive flat rows of each table index: table
    [T·dy, D] + idx [...] → [..., dy, D] (row block [i·dy, (i+1)·dy) for
    index i)."""
    flat = idx[..., None] * dy + jnp.arange(dy)
    return table[flat]


@functools.partial(jax.jit, static_argnames=("dy", "interpret"))
def dekrr_step_reference(g, d, s, p, theta, nbr_idx, self_idx, nbr_mask,
                         *, dy: int = 1, interpret: bool = False):
    """Pure-jnp oracle with the raw kernel's exact contract (padded shapes,
    θ-table indirection, Dy-flattened rows) — what
    `tests/test_kernels_dekrr_step.py` pins the kernel against before any
    repro.dist plumbing is involved."""
    del interpret
    if dy == 1:
        nbr_theta = theta[nbr_idx]                    # [J, K, D]
        coupled = jnp.einsum(
            "jkab,jkb->ja", p,
            nbr_theta * nbr_mask[..., None].astype(theta.dtype))
        own = jnp.einsum("jab,jb->ja", s, theta[self_idx])
        return jnp.einsum("jab,jb->ja", g, d + own + coupled)
    nbr_theta = _table_rows(theta, nbr_idx, dy)       # [J, K, Dy, D]
    coupled = jnp.einsum(
        "jkab,jkob->joa", p,
        nbr_theta * nbr_mask[..., None, None].astype(theta.dtype))
    own = jnp.einsum("jab,job->joa", s, _table_rows(theta, self_idx, dy))
    d3 = d.reshape(-1, dy, d.shape[1])                # [J, Dy, D]
    out = jnp.einsum("jab,job->joa", g, d3 + own + coupled)
    return out.reshape(-1, d.shape[1])


@functools.partial(jax.jit, static_argnames=("dy", "interpret"))
def dekrr_step_masked_reference(g, d, s, p, theta, nbr_idx, self_idx,
                                nbr_mask, active, *, dy: int = 1,
                                interpret: bool = False):
    """Pure-jnp oracle for the activation-masked kernel: nodes with
    active == 0 return their own θ-table rows unchanged; active nodes run
    the unmasked oracle's arithmetic."""
    new = dekrr_step_reference(g, d, s, p, theta, nbr_idx, self_idx,
                               nbr_mask, dy=dy, interpret=interpret)
    if dy == 1:
        return jnp.where((active != 0)[:, None], new, theta[self_idx])
    own = _table_rows(theta, self_idx, dy).reshape(new.shape)
    gate = jnp.repeat(active != 0, dy)[:, None]
    return jnp.where(gate, new, own)
