"""Jit'd public wrappers for the Pallas kernels.

Handles padding/alignment (TPU tiles: sublane 8, lane 128), validity
masking, and backend dispatch: on non-TPU backends the kernels execute in
``interpret=True`` mode (Python evaluation of the kernel body — bit-accurate
semantics, used for CPU validation against ref.py).

Every wrapper also runs the static VMEM budget check from
`repro.analysis.vmem` at the *padded* shapes it is about to dispatch:
an over-budget call raises `VmemBudgetError` naming the working-set
formula and the 16 MiB limit before the kernel is built, instead of an
opaque Mosaic allocation crash. The DeKRR wrappers additionally
bounds-check concrete slot-index tables (scalar prefetch reads SMEM
indices with no hardware bounds check — see `check_index_table`).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.analysis.vmem import (check_index_table,
                                 estimate_dekrr_async_solve,
                                 estimate_dekrr_cheb_solve,
                                 estimate_dekrr_solve, estimate_dekrr_step,
                                 estimate_flash_decode,
                                 estimate_rff_features, estimate_rff_gram)
from repro.core.rff import FeatureMap
from repro.kernels.dekrr_solve import (dekrr_async_solve_pallas,
                                       dekrr_cheb_solve_pallas,
                                       dekrr_solve_pallas)
from repro.kernels.dekrr_step import dekrr_step_pallas
from repro.kernels.rff_features import rff_features_pallas
from repro.kernels.rff_gram import rff_gram_pallas


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_dim(n: int, multiple: int) -> int:
    return max(multiple, -(-int(n) // multiple) * multiple)


def _dekrr_dy(d) -> int:
    """Output width Dy of a DeKRR operand set: d/theta are [.., D] for
    scalar targets or [.., D, Dy] for multi-output."""
    return 1 if d.ndim == 2 else int(d.shape[2])


def _flatten_dy(a: jax.Array) -> jax.Array:
    """[T, D, Dy] → [T·Dy, D] flat-row layout (table row t owns the Dy
    consecutive rows [t·Dy, (t+1)·Dy), i.e. that node's θᵀ); identity for
    2-D scalar-target operands so the Dy = 1 trace is unchanged."""
    if a.ndim == 2:
        return a
    t, d_feat, dy = a.shape
    return a.transpose(0, 2, 1).reshape(t * dy, d_feat)


def _node_rows(a: jax.Array) -> jax.Array:
    """[J, D] → [J, 1, D] and [J, D, Dy] → [J, Dy, D]: the kernels'
    per-node row-block layout for d and the θ outputs (a leading node
    axis, so each grid step's block is the array's whole trailing
    [Dy, D] — a TPU-legal block at any Dy)."""
    return a[:, None, :] if a.ndim == 2 else a.transpose(0, 2, 1)


def _from_node_rows(out: jax.Array, d_feat: int,
                    ndim: int = 2) -> jax.Array:
    """Invert `_node_rows` on a kernel output [J, Dy, D_pad]. Scalar-layout
    operands (ndim == 2) come back [J, d_feat]; trailing-axis operands
    (ndim == 3) restore [J, d_feat, dy] — even at dy == 1, so a [.., 1]
    multi-output layout round-trips with its axis intact."""
    if ndim == 2:
        return out[:, 0, :d_feat]
    return out[:, :, :d_feat].transpose(0, 2, 1)


def _check_tpu_dtype(interpret: bool, *arrays) -> None:
    """Refuse f64 operands for a compiled TPU kernel: the TPU has no f64,
    and Mosaic would fail deep inside lowering. Callers cast at their
    side (the chip path runs f32 with x64 off); interpret mode keeps
    f64 for the CPU parity tests."""
    if interpret:
        return
    for a in arrays:
        if jnp.dtype(a.dtype) == jnp.float64:
            raise ValueError(
                f"Pallas TPU kernels take no float64 operands (got "
                f"{a.dtype} {tuple(a.shape)}): cast to float32 before "
                f"calling a compiled kernel, or pass interpret=True")


def _check_dekrr_budget(kernel: str, d, p, theta) -> None:
    """Static VMEM check at the padded dispatch shapes. Shapes are always
    static (works on tracers), so under jit this runs once at trace time
    and is free at execution time. Multi-output operands fold Dy into the
    flattened table/buffer row counts and the per-step vector term."""
    dy = _dekrr_dy(d)
    d_pad = _pad_dim(d.shape[1], 128)
    t_pad = _pad_dim(theta.shape[0] * dy, 8)
    k_pad = max(int(p.shape[1]), 1)
    j_pad = _pad_dim(d.shape[0] * dy, 8)
    size = jnp.dtype(d.dtype).itemsize
    if kernel == "dekrr_step":
        est = estimate_dekrr_step(t_rows=t_pad, d_feat=d_pad,
                                  k_slots=k_pad, itemsize=size, dy=dy)
    elif kernel == "dekrr_solve":
        est = estimate_dekrr_solve(t_rows=t_pad, d_feat=d_pad,
                                   k_slots=k_pad, itemsize=size, dy=dy)
    elif kernel == "dekrr_async_solve":
        est = estimate_dekrr_async_solve(
            t_rows=t_pad, b_rows=_pad_dim(d.shape[0] * k_pad * dy, 8),
            d_feat=d_pad, k_slots=k_pad, itemsize=size, dy=dy)
    elif kernel == "dekrr_cheb_solve":
        est = estimate_dekrr_cheb_solve(t_rows=t_pad, j_rows=j_pad,
                                        d_feat=d_pad, k_slots=k_pad,
                                        itemsize=size, dy=dy)
    else:  # pragma: no cover - programming error
        raise ValueError(f"unknown DeKRR kernel {kernel!r}")
    est.check()


def _check_dekrr_indices(theta, nbr_idx, self_idx, nbr_mask) -> None:
    """Bounds-check concrete slot tables against the θ-table row count;
    traced tables are validated at the staging layer instead
    (`repro.dist.pack_problem` / `pack_theta`)."""
    t_rows = int(theta.shape[0])
    if not isinstance(self_idx, jax.core.Tracer):
        check_index_table("self_idx", self_idx, t_rows)
    if isinstance(nbr_idx, jax.core.Tracer):
        return
    idx = jnp.asarray(nbr_idx)
    if idx.size and not isinstance(nbr_mask, jax.core.Tracer):
        import numpy as np

        live = np.asarray(nbr_mask) != 0
        if not live.any():
            return
        idx = np.asarray(idx)[live]
    check_index_table("nbr_idx", idx, t_rows)


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("scale", "block_n", "interpret"))
def rff_gram(omega: jax.Array, bias: jax.Array, x: jax.Array, y: jax.Array,
             *, scale: float, block_n: int = 1024,
             interpret: bool | None = None) -> tuple[jax.Array, jax.Array]:
    """Fused streaming (Z Zᵀ, Z yᵀ) for Z = scale·cos(Ω X + b).

    omega [D, d], bias [D], x [d, N], y [N] → (G [D, D], zy [D]).
    """
    if interpret is None:
        interpret = _interpret_default()
    _check_tpu_dtype(interpret, omega, bias, x, y)
    d_feat, n = omega.shape[0], x.shape[1]
    dtype = x.dtype

    bn = min(block_n, max(128, 1 << (n - 1).bit_length()))
    estimate_rff_gram(d_feat=_pad_dim(d_feat, 8),
                      d_in=_pad_dim(omega.shape[1], 128), block_n=bn,
                      itemsize=jnp.dtype(dtype).itemsize).check()
    omega_p = _pad_to(_pad_to(omega, 0, 8), 1, 128)
    bias_p = _pad_to(bias.reshape(-1, 1), 0, 8).astype(dtype)
    x_p = _pad_to(_pad_to(x, 0, 128), 1, bn)
    n_pad = x_p.shape[1]
    mask = (jnp.arange(n_pad) < n).astype(dtype).reshape(1, n_pad)
    y_p = _pad_to(y.reshape(1, -1).astype(dtype), 1, bn)

    gram, zy = rff_gram_pallas(
        omega_p.astype(dtype), bias_p, x_p, y_p, mask,
        scale=scale, block_n=bn, interpret=interpret)
    return gram[:d_feat, :d_feat], zy[:d_feat, 0]


@partial(jax.jit, static_argnames=("scale", "block_d", "block_n",
                                   "interpret"))
def rff_features(omega: jax.Array, bias: jax.Array, x: jax.Array, *,
                 scale: float, block_d: int = 256, block_n: int = 512,
                 interpret: bool | None = None) -> jax.Array:
    """Fused Z = scale·cos(Ω X + b): omega [D, d], x [d, N] → Z [D, N].

    The serving path's featurize kernel: its tiled working set
    (`Bd·d + Bd + d·Bn + Bd·Bn` elements) is checked against the VMEM
    budget before dispatch — over-budget tilings raise `VmemBudgetError`
    instead of a Mosaic allocation crash (`estimate_rff_features`).
    """
    if interpret is None:
        interpret = _interpret_default()
    _check_tpu_dtype(interpret, omega, bias, x)
    d_feat, n = omega.shape[0], x.shape[1]
    dtype = x.dtype

    bd = min(block_d, max(8, 1 << (d_feat - 1).bit_length()))
    bn = min(block_n, max(128, 1 << (n - 1).bit_length()))
    estimate_rff_features(block_d=bd, d_in=_pad_dim(omega.shape[1], 128),
                          block_n=bn,
                          itemsize=jnp.dtype(dtype).itemsize).check()
    omega_p = _pad_to(_pad_to(omega, 0, bd), 1, 128).astype(dtype)
    bias_p = _pad_to(bias.reshape(-1, 1), 0, bd).astype(dtype)
    x_p = _pad_to(_pad_to(x, 0, 128), 1, bn)

    z = rff_features_pallas(omega_p, bias_p, x_p, scale=scale,
                            block_d=bd, block_n=bn, interpret=interpret)
    return z[:d_feat, :n]


@partial(jax.jit, static_argnames=("scale", "compute_dtype", "block_d",
                                   "block_n", "interpret"))
def rff_features_lowp(omega: jax.Array, bias: jax.Array, x: jax.Array, *,
                      scale: float, compute_dtype: str = "bfloat16",
                      block_d: int = 256, block_n: int = 512,
                      interpret: bool | None = None) -> jax.Array:
    """Low-precision serving featurize: Z = scale·cos(Ω X + b) with the
    GEMM and cosine evaluated in ``compute_dtype`` (bf16 by default) and
    the √(2/D) scale applied afterwards in f32.

    This is the mixed-precision serving tier's featurize entry point
    (`repro.serve.dekrr`, precision="bf16"/"int8"): queries run the
    feature map at half width while the solve stays x64. Returns Z in
    float32 regardless of compute dtype; the serving tier's analytic
    forward-error bound assumes exactly this arrangement (low-precision
    Ω/b/X/GEMM/cos, f32 scale multiply), so do not fold the scale into
    the low-precision kernel. Same tiling and VMEM pre-check as
    `rff_features` — at 2-byte elements the working set is half the f32
    path's.
    """
    cdt = jnp.dtype(compute_dtype)
    z = rff_features(omega.astype(cdt), bias.astype(cdt), x.astype(cdt),
                     scale=1.0, block_d=block_d, block_n=block_n,
                     interpret=interpret)
    return z.astype(jnp.float32) * jnp.float32(scale)


@partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 cur_index: jax.Array, *, block_s: int = 512,
                 interpret: bool | None = None) -> jax.Array:
    """Single-token decode attention with the flash-decode kernel.

    q [B, 1, H, dh], k/v [B, S, K, dh] (GQA: H % K == 0), cur_index [] —
    returns [B, 1, H, dh]. Rows are (batch, kv-head) pairs; dh pads to 128,
    S pads to block_s (padded positions are masked by cur_index).
    """
    from repro.kernels.decode_attention import flash_decode_pallas

    if interpret is None:
        interpret = _interpret_default()
    out_dtype = q.dtype
    if q.dtype == jnp.float64:          # no f64 on TPU; x64-mode callers
        q = q.astype(jnp.float32)
        k_cache = k_cache.astype(jnp.float32)
        v_cache = v_cache.astype(jnp.float32)
    b, _, h, dh = q.shape
    s, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    scale = dh ** -0.5
    bs = min(block_s, max(128, 1 << (s - 1).bit_length()))
    estimate_flash_decode(g_heads=g, head_dim=_pad_dim(dh, 128),
                          block_s=bs, itemsize=4).check()

    # [B, 1, H, dh] → [B·K, G, dh]
    qr = q[:, 0].reshape(b, kh, g, dh).reshape(b * kh, g, dh)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(b * kh, s, dh)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(b * kh, s, dh)
    qr = _pad_to(qr, 2, 128)
    kr = _pad_to(_pad_to(kr, 1, bs), 2, 128)
    vr = _pad_to(_pad_to(vr, 1, bs), 2, 128)
    lens = jnp.broadcast_to(cur_index.astype(jnp.int32),
                            (b * kh, 1))
    out = flash_decode_pallas(qr, kr, vr, lens, scale=scale,
                              block_s=bs, interpret=interpret)
    out = out[:, :, :dh].reshape(b, kh, g, dh).reshape(b, 1, h, dh)
    return out.astype(out_dtype)


def _pad_dekrr_operands(g, d, s, p, theta, nbr_idx, nbr_mask):
    """Shared operand padding for the DeKRR round/solve kernels: D to lane
    multiples of 128, the θ table to sublane multiples of 8, the slot axis
    to K ≥ 1 (an all-masked zero-P slot for edgeless graphs), index/mask
    tables coerced to int32. d goes to the kernels' [J, Dy, D] node-row
    layout and the θ table to the flat [T·Dy, D] one (identity at
    Dy = 1). One helper so `dekrr_step` and `dekrr_solve` can never
    drift apart on the operand layout."""
    j_nodes = d.shape[0]
    g_p = _pad_to(_pad_to(g, 1, 128), 2, 128)
    s_p = _pad_to(_pad_to(s, 1, 128), 2, 128)
    d_p = _pad_to(_node_rows(d), 2, 128)
    p_p = _pad_to(_pad_to(p, 2, 128), 3, 128)
    if p_p.shape[1] == 0:                       # K = 0 (edgeless graph)
        p_p = jnp.zeros((j_nodes, 1) + p_p.shape[2:], p_p.dtype)
        nbr_idx = jnp.zeros((j_nodes, 1), jnp.int32)
        nbr_mask = jnp.zeros((j_nodes, 1), jnp.int32)
    theta_p = _pad_to(_pad_to(_flatten_dy(theta), 1, 128), 0, 8)
    return (g_p, d_p, s_p, p_p, theta_p, nbr_idx.astype(jnp.int32),
            (nbr_mask != 0).astype(jnp.int32))


@partial(jax.jit, static_argnames=("interpret",))
def _dekrr_step_jit(g, d, s, p, theta, nbr_idx, self_idx, nbr_mask,
                    active=None, *, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    _check_tpu_dtype(interpret, g, d, s, p, theta)
    d_feat = d.shape[1]

    g_p, d_p, s_p, p_p, theta_p, nbr_idx_p, nbr_mask_p = \
        _pad_dekrr_operands(g, d, s, p, theta, nbr_idx, nbr_mask)
    active_p = None if active is None else (active != 0).astype(jnp.int32)
    out = dekrr_step_pallas(
        g_p, d_p, s_p, p_p, theta_p,
        nbr_idx_p, self_idx.astype(jnp.int32), nbr_mask_p,
        active=active_p, interpret=interpret)
    return _from_node_rows(out, d_feat, d.ndim)


def dekrr_step(g: jax.Array, d: jax.Array, s: jax.Array, p: jax.Array,
               theta: jax.Array, nbr_idx: jax.Array, self_idx: jax.Array,
               nbr_mask: jax.Array, active: jax.Array | None = None, *,
               interpret: bool | None = None) -> jax.Array:
    """Fused packed Eq. 19 round: θ_j ← G_j(d_j + S_j θ_sj + Σ m P_jk θ_rk).

    g/s [J, D, D], d [J, D], p [J, K, D, D], theta [T, D] (θ table),
    nbr_idx [J, K] / self_idx [J] rows into the table, nbr_mask [J, K]
    (any dtype; nonzero = live slot) → [J, D]. Multi-output targets add
    a trailing axis: d [J, D, Dy] / theta [T, D, Dy] → [J, D, Dy]
    (internally flattened to the kernel's [rows·Dy, D] layout; the Dy = 1
    trace is today's scalar path, bit-for-bit).

    ``active`` ([J], any dtype, optional) runs the activation-masked async
    variant: nodes with active[j] == 0 return their θ-table row unchanged
    (`repro.dist.async_gossip`); with active omitted or all-ones the
    synchronous kernel arithmetic runs bit-for-bit.

    Pads D to lane multiples of 128, the θ table to sublane multiples of 8
    and the slot axis to K ≥ 1 (an all-masked zero-P slot), then slices the
    padding back off. Zero padding is exact under the round's algebra (see
    `repro.dist.dekrr_spmd`), so this matches `step_batched` to the last
    ulp-scale rounding of the reordered contractions (rtol 1e-9 under x64).

    VMEM working set at the padded shapes is `T·D + (2+K)·D² + 3·D`
    elements (consolidated table: `repro.analysis.vmem`); over-budget
    shapes raise `VmemBudgetError` here, before dispatch. Concrete
    (non-traced) `nbr_idx`/`self_idx` tables are bounds-checked against
    the θ-table row count.
    """
    _check_dekrr_budget("dekrr_step", d, p, theta)
    _check_dekrr_indices(theta, nbr_idx, self_idx, nbr_mask)
    return _dekrr_step_jit(g, d, s, p, theta, nbr_idx, self_idx, nbr_mask,
                           active, interpret=interpret)


@partial(jax.jit, static_argnames=("num_rounds", "trace", "interpret"))
def _dekrr_solve_jit(g, d, s, p, theta, nbr_idx, self_idx, nbr_mask, *,
                     num_rounds, trace=False, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    d_feat = d.shape[1]
    self_idx = self_idx.astype(jnp.int32)
    if num_rounds == 0:
        out0 = theta[self_idx]
        if trace:
            return out0, jnp.zeros((0, d.shape[0]), theta.dtype)
        return out0
    _check_tpu_dtype(interpret, g, d, s, p, theta)

    g_p, d_p, s_p, p_p, theta_p, nbr_idx_p, nbr_mask_p = \
        _pad_dekrr_operands(g, d, s, p, theta, nbr_idx, nbr_mask)
    out = dekrr_solve_pallas(
        g_p, d_p, s_p, p_p, theta_p, nbr_idx_p, self_idx, nbr_mask_p,
        num_rounds=num_rounds, trace=trace, interpret=interpret)
    if trace:
        out, res = out
        return _from_node_rows(out, d_feat, d.ndim), res[:, 0]
    return _from_node_rows(out, d_feat, d.ndim)


def dekrr_solve(g: jax.Array, d: jax.Array, s: jax.Array, p: jax.Array,
                theta: jax.Array, nbr_idx: jax.Array, self_idx: jax.Array,
                nbr_mask: jax.Array, *, num_rounds: int,
                trace: bool = False, interpret: bool | None = None
                ) -> jax.Array | tuple[jax.Array, jax.Array]:
    """Fused multi-round Eq. 19 solve: `num_rounds` Jacobi rounds in ONE
    pallas_call, θ tables VMEM-resident across rounds (grid = (R, J),
    `repro.kernels.dekrr_solve`).

    Same operand contract as `dekrr_step` — g/s [J, D, D], d [J, D],
    p [J, K, D, D], theta [T, D] θ table, nbr_idx [J, K] / self_idx [J]
    rows into the table, nbr_mask [J, K] — plus static `num_rounds`.
    Returns the [J, D] θ rows after the last round; table rows owned by
    no node stay at their θ0 values throughout (oracle semantics).
    Multi-output: d [J, D, Dy] / theta [T, D, Dy] → [J, D, Dy].

    Pads exactly like `dekrr_step` (D to 128 lanes, table to 8 sublanes,
    slot axis to K ≥ 1) and slices the padding back off; `num_rounds=0`
    returns the `self_idx` rows of θ unchanged.

    Static ``trace`` appends a res [R, J] convergence-trace array —
    res[r, j] = max|Δθ_j| of round r, written by the same grid steps
    (zero extra dispatches; `num_rounds=0` returns an empty [0, J]).

    VMEM working set at the padded shapes is `2·T·D + 2·(2+K)·D² + 3·D`
    elements — double the step kernel's θ/block terms for the
    round-parity scratch tables and double-buffered streams
    (consolidated table: `repro.analysis.vmem`); over-budget shapes
    raise `VmemBudgetError` here, before dispatch. Concrete
    `nbr_idx`/`self_idx` tables are bounds-checked against the θ-table
    row count.
    """
    if num_rounds != 0:
        _check_dekrr_budget("dekrr_solve", d, p, theta)
    _check_dekrr_indices(theta, nbr_idx, self_idx, nbr_mask)
    return _dekrr_solve_jit(g, d, s, p, theta, nbr_idx, self_idx, nbr_mask,
                            num_rounds=num_rounds, trace=trace,
                            interpret=interpret)


def _check_async_nbr_indices(j_nodes, nbr_idx, nbr_mask) -> None:
    """Async variant of `_check_dekrr_indices`: nbr_idx entries are NODE
    ids — they index the [J] SMEM broadcast-flag vectors as well as θ
    rows — so live slots must lie in [0, J), not merely within the padded
    θ table. Concrete tables only; traced ones are validated at the
    staging layer (`repro.dist.pack_problem`)."""
    if isinstance(nbr_idx, jax.core.Tracer):
        return
    import numpy as np

    idx = np.asarray(nbr_idx)
    if idx.size and not isinstance(nbr_mask, jax.core.Tracer):
        live = np.asarray(nbr_mask) != 0
        if not live.any():
            return
        idx = idx[live]
    check_index_table("nbr_idx", idx, j_nodes)


@partial(jax.jit, static_argnames=("gossip", "censored", "trace",
                                   "interpret"))
def _dekrr_async_solve_jit(g, d, s, p, theta, sent, buffers, nbr_idx,
                           nbr_mask, active_tab, thresholds, *, gossip,
                           censored, trace=False, interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    _check_tpu_dtype(interpret, g, d, s, p, theta, sent, buffers,
                     thresholds)
    j_nodes, d_feat = d.shape[0], d.shape[1]
    dy = _dekrr_dy(d)
    k_in = buffers.shape[1]
    num_rounds = active_tab.shape[0]

    g_p, d_p, s_p, p_p, theta_p, nbr_idx_p, nbr_mask_p = \
        _pad_dekrr_operands(g, d, s, p, theta, nbr_idx, nbr_mask)
    k_pad = p_p.shape[1]
    sent_p = _pad_to(_pad_to(_flatten_dy(sent), 1, 128), 0, 8)
    if k_in:
        buf = buffers
    else:
        tail = (d_feat,) if d.ndim == 2 else (d_feat, dy)
        buf = jnp.zeros((j_nodes, k_pad) + tail, buffers.dtype)
    if buf.ndim == 3:
        buf_flat = buf.reshape(j_nodes * k_pad, d_feat)
    else:
        buf_flat = buf.transpose(0, 1, 3, 2).reshape(
            j_nodes * k_pad * dy, d_feat)
    buf_p = _pad_to(_pad_to(buf_flat, 1, 128), 0, 8)
    outs = dekrr_async_solve_pallas(
        g_p, d_p, s_p, p_p, theta_p, sent_p, buf_p, nbr_idx_p, nbr_mask_p,
        (active_tab != 0).astype(jnp.int32), thresholds.astype(d.dtype),
        censored=censored, edge_gossip=(gossip == "edge"),
        trace=trace, interpret=interpret)
    out_theta, out_sent, out_buf = outs[:3]
    out_buf = out_buf.reshape(j_nodes, k_pad, dy, -1)[:, :k_in, :, :d_feat]
    if d.ndim == 2:
        out_buf = out_buf[:, :, 0]
    else:
        out_buf = out_buf.transpose(0, 1, 3, 2)
    state = (_from_node_rows(out_theta, d_feat, d.ndim),
             _from_node_rows(out_sent, d_feat, d.ndim), out_buf)
    if trace:
        # Drop the delivery-flush row — it computes no round.
        res, bc = outs[3], outs[4]
        return state + (res[:num_rounds, 0], bc[:num_rounds, 0])
    return state


def dekrr_async_solve(g: jax.Array, d: jax.Array, s: jax.Array,
                      p: jax.Array, theta: jax.Array, sent: jax.Array,
                      buffers: jax.Array, nbr_idx: jax.Array,
                      nbr_mask: jax.Array, active_tab: jax.Array,
                      thresholds: jax.Array, *, gossip: str = "bernoulli",
                      censored: bool = False, trace: bool = False,
                      interpret: bool | None = None
                      ) -> tuple[jax.Array, ...]:
    """Fused async-gossip chain: the whole R-round COKE schedule in ONE
    pallas_call (`repro.kernels.dekrr_solve._dekrr_async_solve_kernel`).

    Same block contract as `dekrr_step` — g/s [J, D, D], d [J, D],
    p [J, K, D, D], nbr_idx/nbr_mask [J, K] — but θ indexing is by node
    id (row j = node j, no self_idx indirection): theta/sent [J, D],
    buffers [J, K, D] staleness buffers (slot (j, k) holds the last θ
    received from nbr_idx[j, k]). The precomputed schedule is
    active_tab [R, J] (nonzero = node active in that round) and
    thresholds [R] (censor thresholds; ignored when ``censored`` is
    False). ``gossip`` ∈ {"bernoulli", "edge"} selects whether delivery
    additionally requires the receiver active (edge gossip).

    Returns the post-schedule (theta [J, D], sent [J, D],
    buffers [J, K, D]) — exactly the `AsyncGossipState` fields, so chunked
    callers chain bit-exactly. R = 0 returns the state unchanged.
    Multi-output: d/theta/sent gain a trailing Dy axis and buffers become
    [J, K, D, Dy]; the in-kernel censor reduction runs over features AND
    outputs, matching `repro.dist.async_gossip`.

    Static ``trace`` appends (res [R, J] float, bc [R, J] int32) —
    per-(round, node) max|Δθ| and broadcast flags (0/0 for inactive
    nodes), written by the same grid steps (zero extra dispatches;
    R = 0 returns empty [0, J] arrays). The caller derives the wire
    series (deliveries, bytes) from bc + the slot tables in plain XLA.

    The in-kernel round replays `repro.dist.async_gossip._async_round`'s
    operation sequence, so the chain is bit-for-bit the scanned per-round
    masked kernel (and, at p = 1 uncensored, the sync fused solve).

    VMEM working set at the padded shapes is
    `5·T·D + 2·B·D + 2·(2+K)·D² + 3·D` elements (B = J·K buffer rows;
    consolidated table: `repro.analysis.vmem`); over-budget shapes raise
    `VmemBudgetError` here, before dispatch.
    """
    if gossip not in ("bernoulli", "edge"):
        raise ValueError(f"gossip must be 'bernoulli' or 'edge', "
                         f"got {gossip!r}")
    _check_async_nbr_indices(int(d.shape[0]), nbr_idx, nbr_mask)
    if int(active_tab.shape[0]) == 0:
        if trace:
            j_nodes = int(d.shape[0])
            return (theta, sent, buffers,
                    jnp.zeros((0, j_nodes), theta.dtype),
                    jnp.zeros((0, j_nodes), jnp.int32))
        return theta, sent, buffers
    _check_dekrr_budget("dekrr_async_solve", d, p, theta)
    return _dekrr_async_solve_jit(
        g, d, s, p, theta, sent, buffers, nbr_idx, nbr_mask, active_tab,
        thresholds, gossip=gossip, censored=censored, trace=trace,
        interpret=interpret)


@partial(jax.jit, static_argnames=("trace", "interpret"))
def _dekrr_cheb_solve_jit(g, d, s, p, theta, delta, nbr_idx, self_idx,
                          nbr_mask, alphas, betas, *, trace=False,
                          interpret=None):
    if interpret is None:
        interpret = _interpret_default()
    _check_tpu_dtype(interpret, g, d, s, p, theta, delta, alphas, betas)
    d_feat = d.shape[1]

    g_p, d_p, s_p, p_p, theta_p, nbr_idx_p, nbr_mask_p = \
        _pad_dekrr_operands(g, d, s, p, theta, nbr_idx, nbr_mask)
    delta_p = _pad_to(_pad_to(_flatten_dy(delta), 1, 128), 0, 8)
    outs = dekrr_cheb_solve_pallas(
        g_p, d_p, s_p, p_p, theta_p, delta_p, nbr_idx_p,
        self_idx.astype(jnp.int32), nbr_mask_p,
        alphas.astype(d.dtype), betas.astype(d.dtype),
        trace=trace, interpret=interpret)
    out = (_from_node_rows(outs[0], d_feat, d.ndim),
           _from_node_rows(outs[1], d_feat, d.ndim))
    if trace:
        return out + (outs[2][:, 0],)
    return out


def dekrr_cheb_solve(g: jax.Array, d: jax.Array, s: jax.Array,
                     p: jax.Array, theta: jax.Array, delta: jax.Array,
                     nbr_idx: jax.Array, self_idx: jax.Array,
                     nbr_mask: jax.Array, alphas: jax.Array,
                     betas: jax.Array, *, trace: bool = False,
                     interpret: bool | None = None
                     ) -> tuple[jax.Array, ...]:
    """Fused Chebyshev semi-iteration: R accelerated Eq. 19 rounds in ONE
    pallas_call (`repro.kernels.dekrr_solve._dekrr_cheb_solve_kernel`).

    Same operand contract as `dekrr_solve` — g/s [J, D, D], d [J, D],
    p [J, K, D, D], theta [T, D] θ table, nbr_idx [J, K] / self_idx [J]
    rows into the table, nbr_mask [J, K] — plus delta [J, D] (each node's
    two-term recurrence direction state p, with Δ_k = α_k p_k) and the
    precomputed [R] (α, β) schedule from
    `repro.core.acceleration.chebyshev_coefficients` (R static via
    the schedule length). Returns the (θ rows [J, D], p rows [J, D])
    after the schedule, so chunked callers chain bit-exactly; R = 0
    returns (theta[self_idx], delta) unchanged. Multi-output:
    d/theta/delta gain a trailing Dy axis → ([J, D, Dy], [J, D, Dy]).

    Static ``trace`` appends res [R, J] — per-(round, node) max|Δθ| of
    the accelerated update (the actual step α_r p, not the F-residual),
    written by the same grid steps (zero extra dispatches; R = 0 returns
    an empty [0, J]).

    VMEM working set at the padded shapes is
    `3·T·D + 2·J'·D + 2·(2+K)·D² + 3·D` elements (consolidated table:
    `repro.analysis.vmem`); over-budget shapes raise `VmemBudgetError`
    here, before dispatch.
    """
    if int(alphas.shape[0]) == 0:
        if trace:
            return (theta[self_idx], delta,
                    jnp.zeros((0, int(d.shape[0])), theta.dtype))
        return theta[self_idx], delta
    _check_dekrr_budget("dekrr_cheb_solve", d, p, theta)
    _check_dekrr_indices(theta, nbr_idx, self_idx, nbr_mask)
    return _dekrr_cheb_solve_jit(g, d, s, p, theta, delta, nbr_idx,
                                 self_idx, nbr_mask, alphas, betas,
                                 trace=trace, interpret=interpret)


# ---------------------------------------------------------------- integration
def gram_fn_for_solver(fmap: FeatureMap, x: jax.Array) -> jax.Array:
    """Drop-in ``gram_fn`` for DeKRRSolver: computes Z(Ω, X) Z(Ω, X)ᵀ with the
    fused kernel (cos_bias maps only; f32)."""
    if fmap.kind != "cos_bias":
        raise NotImplementedError("fused gram kernel supports cos_bias maps")
    scale = float(jnp.sqrt(2.0 / fmap.num_frequencies))
    dtype = jnp.float32
    g, _ = rff_gram(fmap.omega.astype(dtype), fmap.bias.astype(dtype),
                    x.astype(dtype), jnp.zeros(x.shape[1], dtype),
                    scale=scale)
    return g.astype(x.dtype)
