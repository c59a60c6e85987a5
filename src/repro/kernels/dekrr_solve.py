"""Fused multi-round DeKRR solve (Eq. 19) — one Pallas TPU kernel.

`repro.kernels.dekrr_step` fuses one Eq. 19 round; the solve is still a
`lax.scan` around it, which means one kernel dispatch per round and one
HBM round-trip of the θ table per round. The paper's operating points have
ρ(M) ≈ 0.95–0.999, i.e. hundreds-to-thousands of rounds, so once the round
itself is fused the per-round launch/dispatch overhead is what's left on
the table. This kernel runs the *entire* solve in one `pallas_call`:

    grid = (R, J)  — rounds outer, nodes inner (row-major, j fastest):
      θ0 table    [T, D]        fetched once (constant index map)
      G_j, S_j    [1, D, D]     streamed per (r, j) step — the index map
      P_j         [1, K, D, D]  depends only on j, so the Pallas pipeline
      d_j         [Dy, D]       double-buffers the HBM→VMEM block streams
                                across steps and rounds
      scratch     2 × [T, D]    VMEM θ tables (even/odd round parity)

Jacobi needs two θ tables: every node in round r reads the table round
r−1 wrote. The two VMEM scratch tables alternate roles by round parity —
round r reads table r mod 2 and writes table (r+1) mod 2. Both are
initialized from θ0 at the first grid step so that table rows owned by no
node (T > J callers) stay at their θ0 values under either parity, exactly
as the pure-jnp oracle keeps them. θ never touches HBM between rounds;
the only per-round HBM traffic is the [J, D, D] block re-streaming, which
is inherent (the blocks do not fit in VMEM for production J·D²) and is
hidden behind the MXU by the pipeline.

The per-step arithmetic — scalar-prefetched slot-table neighbor gather,
row-vector dot_general contractions, zero-padding closure — is the one
`dekrr_step._eq19_update` body every round kernel shares; the parity
suite pins this kernel to `solve_batched(backend="xla")` and the ragged
reference at rtol 1e-9 under x64 (`tests/test_kernels_dekrr_solve.py`).
Per-node blocks and the per-(round, node) trace rows follow the TPU
block rules described in `repro.kernels.dekrr_step`.

VMEM working set: 2·T·D (θ tables) + 2·(2 + K)·D² (double-buffered
blocks) + 3·D vectors — for the paper's J ≤ 256, D ≤ 512, K = 4 at f32
that is ~13.7 MB, within the 16 MB/core budget (J = 256 at D = 512 is
the ceiling; larger tables need a block-sharded θ layout). This formula
is executable as `repro.analysis.vmem.estimate_dekrr_solve`
(consolidated table in that module's docstring); the `ops.dekrr_solve`
wrapper checks it before dispatch and raises `VmemBudgetError` instead
of a Mosaic allocation crash. All dims must be padded by the wrapper:
D to lane multiples of 128, T to sublane multiples of 8.

Two sibling kernels fuse the other two solve schedules the same way —
both are precomputable per chunk, so the per-round control flow that used
to force one dispatch per round rides scalar prefetch instead:

  * `_dekrr_async_solve_kernel` — the COKE async-gossip chain
    (`repro.dist.async_gossip`): the [R, J] activation table and [R]
    censor thresholds prefetch like the slot tables; sent/staleness-buffer
    state lives in VMEM scratch and broadcast flags in two round-parity
    [J] SMEM vectors. Bit-for-bit the scanned per-round masked kernel.
  * `_dekrr_cheb_solve_kernel` — the Chebyshev semi-iteration
    (`repro.core.acceleration`): the precomputed (α_k, β_k) recurrence
    tables prefetch as two [R] float vectors and the two-term Δ state is
    a VMEM table, so the accelerated O(√κ)-round solve is also one
    dispatch per chunk.

Their VMEM working sets are `estimate_dekrr_async_solve` /
`estimate_dekrr_cheb_solve` in `repro.analysis.vmem`.

Multi-output targets (Dy > 1) use the layout of
`repro.kernels.dekrr_step`: θ/sent/Δ tables arrive as [T·Dy, D] with
table row t owning flat rows [t·Dy, (t+1)·Dy) (that node's θᵀ as a
[Dy, D] block), staleness buffers as [B·Dy, D] with slot (j, k) at rows
[(j·K + k)·Dy, ...), and d and the per-node outputs as [J, Dy, D]
(buffers [J, K·Dy, D]). Every kernel derives Dy from the d block's
sublane extent and scales its dynamic row reads. The censor reduction
max|new − sent| runs over the [Dy, D] block, i.e. the max over features
AND outputs the async runtime documents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dekrr_step import (_eq19_update, _rows, _set_lane,
                                      _set_rows, _table_update,
                                      dekrr_step_reference)


def _dekrr_solve_kernel(nbr_idx_ref, self_idx_ref, nbr_mask_ref,
                        theta0_ref, g_ref, d_ref, s_ref, p_ref, *refs,
                        trace: bool = False):
    """One node's Eq. 19 update at grid position (round, node).

    Scalar prefetch (SMEM): nbr_idx [J, K] int32, self_idx [J] int32,
    nbr_mask [J, K] int32. Tensor operands: theta0 [T·Dy, D] (full table,
    fetched once), g/s [1, D, D], d [Dy, D], p [1, K, D, D]; out [Dy, D]
    (node j's θ rows, overwritten every round — the last round wins).
    Scratch: tab_even/tab_odd [T·Dy, D] VMEM θ tables, alternating by
    round parity.

    With static ``trace`` set, a second output block res [1, J] — round
    r's row of the [R, 1, J] residual array, resident across the node
    axis — gets lane j = max|new − θ_self| over the node's [Dy, D] block:
    the per-(round, node) convergence residual, written by the same grid
    step that computes the round (zero extra dispatches). Padded
    coordinates are identically zero on both sides of the subtraction,
    so the max is exact over real coordinates.
    """
    if trace:
        (out_ref, out_res_ref, tab_even_ref, tab_odd_ref) = refs
    else:
        (out_ref, tab_even_ref, tab_odd_ref) = refs
        out_res_ref = None
    r = pl.program_id(0)
    j = pl.program_id(1)
    dy = d_ref.shape[0]

    @pl.when(jnp.logical_and(r == 0, j == 0))
    def _init():
        # Both parities start from θ0 so rows no node owns stay at θ0.
        tab_even_ref[...] = theta0_ref[...]
        tab_odd_ref[...] = theta0_ref[...]

    def round_body(read_ref, write_ref):
        theta_self, new = _table_update(j, nbr_idx_ref, self_idx_ref,
                                        nbr_mask_ref, read_ref, g_ref,
                                        d_ref, s_ref, p_ref)
        _set_rows(write_ref, self_idx_ref[j] * dy, new)
        out_ref[...] = new
        if trace:
            _set_lane(out_res_ref, j, jnp.max(jnp.abs(new - theta_self)))

    even_round = r % 2 == 0

    @pl.when(even_round)
    def _even():
        round_body(tab_even_ref, tab_odd_ref)

    @pl.when(jnp.logical_not(even_round))
    def _odd():
        round_body(tab_odd_ref, tab_even_ref)


def _node_rows(dy: int, d_feat: int, rows: int = 1):
    """BlockSpec of node j's [rows·Dy, D] block of a [J, rows·Dy, D]
    array on a (round, node) grid."""
    return pl.BlockSpec((None, rows * dy, d_feat), lambda r, j, *_: (j, 0, 0))


def _round_row(j_nodes: int):
    """BlockSpec of round r's [1, J] row of an [R, 1, J] per-(round, node)
    array — resident across the node axis, filled lane by lane."""
    return pl.BlockSpec((None, 1, j_nodes), lambda r, j, *_: (r, 0, 0))


def dekrr_solve_pallas(g: jax.Array, d: jax.Array, s: jax.Array,
                       p: jax.Array, theta: jax.Array, nbr_idx: jax.Array,
                       self_idx: jax.Array, nbr_mask: jax.Array, *,
                       num_rounds: int, trace: bool = False,
                       interpret: bool = False) -> jax.Array:
    """Raw pallas_call. All dims must already be padded/aligned:

      g/s [J, D, D], d [J, Dy, D], p [J, K, D, D] with K ≥ 1 and D a
      multiple of 128; theta [T·Dy, D] with T·Dy padded to a multiple of
      8; nbr_idx [J, K] int32 *table* rows (pre-flattening); self_idx [J]
      int32 (distinct rows); nbr_mask [J, K] int32; num_rounds ≥ 1 static.
    Returns the θ rows after `num_rounds` Jacobi rounds, [J, Dy, D]
    (callers with T ≠ J re-assemble their table themselves). With
    ``trace`` set, returns (θ rows, res [R, 1, J]) where res[r, 0, j] =
    max|Δθ_j| of round r — same single dispatch.
    """
    j_nodes, dy, d_feat = d.shape
    k_slots = p.shape[1]
    t_rows = theta.shape[0]
    assert d_feat % 128 == 0 and t_rows % 8 == 0, (d_feat, t_rows)
    assert k_slots >= 1, "pad the slot axis to K >= 1 (zero P blocks)"
    assert num_rounds >= 1, "num_rounds must be a positive static int"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # nbr_idx, self_idx, nbr_mask
        grid=(num_rounds, j_nodes),
        in_specs=[
            pl.BlockSpec((t_rows, d_feat), lambda r, j, *_: (0, 0)),  # θ0
            pl.BlockSpec((1, d_feat, d_feat), lambda r, j, *_: (j, 0, 0)),
            _node_rows(dy, d_feat),
            pl.BlockSpec((1, d_feat, d_feat), lambda r, j, *_: (j, 0, 0)),
            pl.BlockSpec((1, k_slots, d_feat, d_feat),
                         lambda r, j, *_: (j, 0, 0, 0)),
        ],
        out_specs=((_node_rows(dy, d_feat), _round_row(j_nodes))
                   if trace else _node_rows(dy, d_feat)),
        scratch_shapes=[
            pltpu.VMEM((t_rows, d_feat), theta.dtype),   # even-round table
            pltpu.VMEM((t_rows, d_feat), theta.dtype),   # odd-round table
        ],
    )
    theta_shape = jax.ShapeDtypeStruct((j_nodes, dy, d_feat), theta.dtype)
    res_shape = jax.ShapeDtypeStruct((num_rounds, 1, j_nodes), theta.dtype)
    flops_per_node = 2 * (2 + k_slots) * d_feat * d_feat * dy
    return pl.pallas_call(
        functools.partial(_dekrr_solve_kernel, trace=trace),
        grid_spec=grid_spec,
        out_shape=(theta_shape, res_shape) if trace else theta_shape,
        cost_estimate=pl.CostEstimate(
            flops=num_rounds * j_nodes * flops_per_node,
            bytes_accessed=(t_rows * d_feat            # θ0, fetched once
                            + num_rounds * j_nodes
                            * ((3 + k_slots) * d_feat * d_feat
                               + dy * d_feat)
                            ) * theta.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(nbr_idx, self_idx, nbr_mask, theta, g, d, s, p)


# --------------------------------------------------------------- async chain
def _dekrr_async_solve_kernel(nbr_idx_ref, nbr_mask_ref, active_ref, thr_ref,
                              theta0_ref, sent0_ref, buf0_ref, g_ref, d_ref,
                              s_ref, p_ref, *refs, censored: bool,
                              edge_gossip: bool, num_rounds: int,
                              trace: bool = False):
    """R censored async-gossip rounds in one kernel; grid (R + 1, J).

    The whole COKE schedule is precomputed, so it rides scalar prefetch:
    nbr_idx [J, K] int32 (NODE ids, not table rows — self row of node j is
    row j), nbr_mask [J, K] int32, active [R, J] int32 activation table,
    thr [R] float censor thresholds. Tensor operands: theta0/sent0
    [T·Dy, D] and buf0 [B·Dy, D] initial state (constant index maps,
    fetched once), g/s [1, D, D], d [Dy, D], p [1, K, D, D] streamed per
    (r, j).

    State lives in scratch across the whole grid: two round-parity θ
    tables (Jacobi semantics, as in the sync kernel), a sent table and a
    flattened staleness-buffer table (owner-only access — no parity
    needed), and two parity [J] SMEM broadcast-flag vectors (node j at
    step r can already have overwritten its round-r flag when a later
    node j' > j of the *same* step reads flags, so flags alternate parity
    exactly like θ).

    Step (r, j) replays `repro.dist.async_gossip._async_round` for node j
    with round r − 1's deliveries applied first:

      deliver (r ≥ 1): slot k receives iff the slot is live and
        broadcaster nbr_idx[j, k] raised its round r − 1 flag (edge
        gossip additionally requires receiver j active in round r − 1);
        the buffer row copies the broadcaster's post-round-(r−1) θ row.
      compute (r < R): active nodes run the exact `_eq19_update`
        arithmetic with neighbor rows read from the staleness buffer;
        censored mode broadcasts iff max|new − sent| > thr[r], updating
        sent on broadcast. Inactive nodes copy θ through and clear their
        flag. Round R is delivery-only (flush of the last broadcasts).

    The arithmetic sequence is identical to the per-round masked kernel
    on the [θ; buffers] concat table, so the chain is bit-for-bit the
    scanned per-round "pallas" backend.

    With static ``trace`` set, two more output blocks — round r's [1, J]
    rows of res (float) and bc (int32), arrays [R + 1, 1, J] — get lane
    j = max|new − θ_self| and the round's broadcast flag for active nodes
    (0/0 for inactive nodes and the delivery-flush step). Written by the
    same grid steps: zero extra dispatches. The caller slices off the
    flush row and derives the wire series (deliveries, bytes) from the bc
    flags + slot tables in plain XLA.
    """
    if trace:
        (out_theta_ref, out_sent_ref, out_buf_ref, out_res_ref, out_bc_ref,
         tab_even_ref, tab_odd_ref, sent_ref, buf_ref, fl_even_ref,
         fl_odd_ref) = refs
    else:
        (out_theta_ref, out_sent_ref, out_buf_ref, tab_even_ref,
         tab_odd_ref, sent_ref, buf_ref, fl_even_ref, fl_odd_ref) = refs
        out_res_ref = out_bc_ref = None
    r = pl.program_id(0)
    j = pl.program_id(1)
    num_slots = nbr_idx_ref.shape[1]
    dy = d_ref.shape[0]

    @pl.when(jnp.logical_and(r == 0, j == 0))
    def _init():
        tab_even_ref[...] = theta0_ref[...]
        tab_odd_ref[...] = theta0_ref[...]
        sent_ref[...] = sent0_ref[...]
        buf_ref[...] = buf0_ref[...]

    def slot_row(k):
        return (j * num_slots + k) * dy

    def deliver(read_tab, fl_read):
        for k in range(num_slots):
            nb = nbr_idx_ref[j, k]
            cond = jnp.logical_and(nbr_mask_ref[j, k] != 0,
                                   fl_read[nb] != 0)
            if edge_gossip:
                cond = jnp.logical_and(cond, active_ref[r - 1, j] != 0)

            @pl.when(cond)
            def _recv(k=k, nb=nb):
                _set_rows(buf_ref, slot_row(k), _rows(read_tab, nb * dy, dy))

    def compute(read_tab, write_tab, fl_write):
        is_active = active_ref[r, j] != 0

        @pl.when(is_active)
        def _update():
            theta_self = _rows(read_tab, j * dy, dy)             # [Dy, D]
            new = _eq19_update(
                j, theta_self, lambda k: _rows(buf_ref, slot_row(k), dy),
                nbr_mask_ref, g_ref, d_ref, s_ref, p_ref)
            _set_rows(write_tab, j * dy, new)
            out_theta_ref[...] = new
            if trace:
                _set_lane(out_res_ref, j,
                          jnp.max(jnp.abs(new - theta_self)))
            if censored:
                # max over features AND outputs — the [Dy, D] block
                delta = jnp.max(jnp.abs(new - _rows(sent_ref, j * dy, dy)))
                bc = delta > thr_ref[r]
                fl_write[j] = bc.astype(jnp.int32)
                if trace:
                    _set_lane(out_bc_ref, j, bc)

                @pl.when(bc)
                def _bcast():
                    _set_rows(sent_ref, j * dy, new)
            else:
                fl_write[j] = jnp.int32(1)
                if trace:
                    _set_lane(out_bc_ref, j, jnp.int32(1))
                _set_rows(sent_ref, j * dy, new)

        @pl.when(jnp.logical_not(is_active))
        def _passthrough():
            cur = _rows(read_tab, j * dy, dy)
            _set_rows(write_tab, j * dy, cur)
            out_theta_ref[...] = cur
            fl_write[j] = jnp.int32(0)

    def step(read_tab, write_tab, fl_read, fl_write):
        if trace:
            # Defaults every grid step (inactive nodes and the flush row
            # record 0); the active-node update overwrites both.
            _set_lane(out_res_ref, j, jnp.zeros((), out_res_ref.dtype))
            _set_lane(out_bc_ref, j, jnp.int32(0))

        @pl.when(r >= 1)
        def _deliver():
            deliver(read_tab, fl_read)

        @pl.when(r < num_rounds)
        def _compute():
            compute(read_tab, write_tab, fl_write)

        @pl.when(r == num_rounds)
        def _flush():
            out_theta_ref[...] = _rows(read_tab, j * dy, dy)

        out_sent_ref[...] = _rows(sent_ref, j * dy, dy)
        out_buf_ref[...] = _rows(buf_ref, slot_row(0), num_slots * dy)

    even_round = r % 2 == 0

    @pl.when(even_round)
    def _even():
        step(tab_even_ref, tab_odd_ref, fl_even_ref, fl_odd_ref)

    @pl.when(jnp.logical_not(even_round))
    def _odd():
        step(tab_odd_ref, tab_even_ref, fl_odd_ref, fl_even_ref)


def dekrr_async_solve_pallas(g: jax.Array, d: jax.Array, s: jax.Array,
                             p: jax.Array, theta: jax.Array,
                             sent: jax.Array, buffers: jax.Array,
                             nbr_idx: jax.Array, nbr_mask: jax.Array,
                             active_tab: jax.Array, thresholds: jax.Array,
                             *, censored: bool, edge_gossip: bool,
                             trace: bool = False, interpret: bool = False
                             ) -> tuple[jax.Array, ...]:
    """Raw pallas_call. All dims must already be padded/aligned:

      g/s [J, D, D], d [J, Dy, D], p [J, K, D, D] with K ≥ 1 and D a
      multiple of 128; theta/sent [T·Dy, D] with T ≥ J and T·Dy padded to
      a multiple of 8 (rows [j·Dy, (j+1)·Dy) = node j); buffers [B·Dy, D]
      with B ≥ J·K, B·Dy a multiple of 8 (rows [(j·K + k)·Dy, ...) = slot
      (j, k)); nbr_idx/nbr_mask [J, K] int32 with entries < J;
      active_tab [R, J] int32 with R ≥ 1 static; thresholds [R] float.
    Returns the post-schedule (θ rows [J, Dy, D], sent rows [J, Dy, D],
    buffer rows [J, K·Dy, D]). With ``trace`` set, appends
    (res [R + 1, 1, J] float, bc [R + 1, 1, J] int32) — per-(round, node)
    max|Δθ| and broadcast flags, last row (delivery flush) all-zero —
    still one dispatch.
    """
    j_nodes, dy, d_feat = d.shape
    k_slots = p.shape[1]
    t_rows = theta.shape[0]
    b_rows = buffers.shape[0]
    num_rounds = active_tab.shape[0]
    assert d_feat % 128 == 0 and t_rows % 8 == 0 and b_rows % 8 == 0, \
        (d_feat, t_rows, b_rows)
    assert sent.shape == theta.shape, (sent.shape, theta.shape)
    assert b_rows >= j_nodes * k_slots * dy, (b_rows, j_nodes, k_slots, dy)
    assert k_slots >= 1, "pad the slot axis to K >= 1 (zero P blocks)"
    assert num_rounds >= 1, "schedule must cover >= 1 round"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,    # nbr_idx, nbr_mask, active_tab, thresholds
        grid=(num_rounds + 1, j_nodes),       # final step: delivery flush
        in_specs=[
            pl.BlockSpec((t_rows, d_feat), lambda r, j, *_: (0, 0)),  # θ0
            pl.BlockSpec((t_rows, d_feat), lambda r, j, *_: (0, 0)),  # sent0
            pl.BlockSpec((b_rows, d_feat), lambda r, j, *_: (0, 0)),  # buf0
            pl.BlockSpec((1, d_feat, d_feat), lambda r, j, *_: (j, 0, 0)),
            _node_rows(dy, d_feat),
            pl.BlockSpec((1, d_feat, d_feat), lambda r, j, *_: (j, 0, 0)),
            pl.BlockSpec((1, k_slots, d_feat, d_feat),
                         lambda r, j, *_: (j, 0, 0, 0)),
        ],
        out_specs=(
            _node_rows(dy, d_feat),                                   # θ
            _node_rows(dy, d_feat),                                   # sent
            _node_rows(dy, d_feat, rows=k_slots),                     # buf
        ) + ((_round_row(j_nodes), _round_row(j_nodes))               # res, bc
             if trace else ()),
        scratch_shapes=[
            pltpu.VMEM((t_rows, d_feat), theta.dtype),   # even-round table
            pltpu.VMEM((t_rows, d_feat), theta.dtype),   # odd-round table
            pltpu.VMEM((t_rows, d_feat), theta.dtype),   # sent table
            pltpu.VMEM((b_rows, d_feat), theta.dtype),   # staleness buffers
            pltpu.SMEM((j_nodes,), jnp.int32),           # even-round flags
            pltpu.SMEM((j_nodes,), jnp.int32),           # odd-round flags
        ],
    )
    kernel = functools.partial(
        _dekrr_async_solve_kernel, censored=censored,
        edge_gossip=edge_gossip, num_rounds=num_rounds, trace=trace)
    flops_per_node = 2 * (2 + k_slots) * d_feat * d_feat * dy
    rows = jax.ShapeDtypeStruct((j_nodes, dy, d_feat), theta.dtype)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            rows, rows,
            jax.ShapeDtypeStruct((j_nodes, k_slots * dy, d_feat),
                                 theta.dtype),
        ) + ((
            jax.ShapeDtypeStruct((num_rounds + 1, 1, j_nodes), theta.dtype),
            jax.ShapeDtypeStruct((num_rounds + 1, 1, j_nodes), jnp.int32),
        ) if trace else ()),
        cost_estimate=pl.CostEstimate(
            flops=num_rounds * j_nodes * flops_per_node,
            bytes_accessed=((2 * t_rows + b_rows) * d_feat
                            + (num_rounds + 1) * j_nodes
                            * ((3 + k_slots) * d_feat * d_feat
                               + dy * d_feat)
                            ) * theta.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(nbr_idx, nbr_mask, active_tab, thresholds, theta, sent, buffers,
      g, d, s, p)


# ---------------------------------------------------------------- chebyshev
def _dekrr_cheb_solve_kernel(nbr_idx_ref, self_idx_ref, nbr_mask_ref,
                             alpha_ref, beta_ref, theta0_ref, delta0_ref,
                             g_ref, d_ref, s_ref, p_ref, *refs,
                             trace: bool = False):
    """R Chebyshev semi-iteration rounds in one kernel; grid (R, J).

    Identical layout to the plain fused solve — parity-alternating θ
    tables, scalar-prefetched slot tables — plus the precomputed (α, β)
    schedule (`repro.core.acceleration.chebyshev_coefficients`) as two
    [R] float prefetch vectors and a [J'·Dy, D] VMEM table holding each
    node's two-term recurrence direction state p (owner-only access, no
    parity; Δ_k = α_k p_k):

        new  = eq19(θ_read)                      (the F-application)
        p_j  ← (new − θ_j) + β_r p_j
        θ_j  ← θ_j + α_r p_j

    θ and p rows are emitted every round (last round wins) so chunked
    callers can chain bit-exactly — the exact recurrence
    `repro.core.acceleration.chebyshev_scan` runs on the host/XLA paths.

    With static ``trace`` set, one more output block — round r's [1, J]
    row of res [R, 1, J] — gets lane j = max|θ_new − θ_j| (the
    accelerated update's actual step α_r p_j, not the F-residual),
    written by the same grid steps, zero extra dispatches.
    """
    if trace:
        (out_theta_ref, out_delta_ref, out_res_ref, tab_even_ref,
         tab_odd_ref, delta_ref) = refs
    else:
        (out_theta_ref, out_delta_ref, tab_even_ref, tab_odd_ref,
         delta_ref) = refs
        out_res_ref = None
    r = pl.program_id(0)
    j = pl.program_id(1)
    dy = d_ref.shape[0]

    @pl.when(jnp.logical_and(r == 0, j == 0))
    def _init():
        tab_even_ref[...] = theta0_ref[...]
        tab_odd_ref[...] = theta0_ref[...]
        delta_ref[...] = delta0_ref[...]

    def round_body(read_ref, write_ref):
        theta_self, new = _table_update(j, nbr_idx_ref, self_idx_ref,
                                        nbr_mask_ref, read_ref, g_ref,
                                        d_ref, s_ref, p_ref)  # F(θ)_j
        resid = new - theta_self
        p_new = resid + beta_ref[r] * _rows(delta_ref, j * dy, dy)
        th_new = theta_self + alpha_ref[r] * p_new
        _set_rows(write_ref, self_idx_ref[j] * dy, th_new)
        _set_rows(delta_ref, j * dy, p_new)
        out_theta_ref[...] = th_new
        out_delta_ref[...] = p_new
        if trace:
            _set_lane(out_res_ref, j, jnp.max(jnp.abs(th_new - theta_self)))

    even_round = r % 2 == 0

    @pl.when(even_round)
    def _even():
        round_body(tab_even_ref, tab_odd_ref)

    @pl.when(jnp.logical_not(even_round))
    def _odd():
        round_body(tab_odd_ref, tab_even_ref)


def dekrr_cheb_solve_pallas(g: jax.Array, d: jax.Array, s: jax.Array,
                            p: jax.Array, theta: jax.Array,
                            delta: jax.Array, nbr_idx: jax.Array,
                            self_idx: jax.Array, nbr_mask: jax.Array,
                            alphas: jax.Array, betas: jax.Array, *,
                            trace: bool = False, interpret: bool = False
                            ) -> tuple[jax.Array, ...]:
    """Raw pallas_call. Same operand contract as `dekrr_solve_pallas`,
    plus delta [J'·Dy, D] (J' ≥ J, J'·Dy a multiple of 8, rows
    [j·Dy, (j+1)·Dy) = node j's direction state p) and the [R] float
    (α, β) schedule with R ≥ 1 static. Returns the (θ rows [J, Dy, D],
    p rows [J, Dy, D]) after R Chebyshev rounds. With ``trace`` set,
    appends res [R, 1, J] — per-(round, node) max|Δθ| of the accelerated
    update — same single dispatch.
    """
    j_nodes, dy, d_feat = d.shape
    k_slots = p.shape[1]
    t_rows = theta.shape[0]
    j_rows = delta.shape[0]
    num_rounds = alphas.shape[0]
    assert d_feat % 128 == 0 and t_rows % 8 == 0 and j_rows % 8 == 0, \
        (d_feat, t_rows, j_rows)
    assert j_rows >= j_nodes * dy, (j_rows, j_nodes, dy)
    assert alphas.shape == betas.shape, (alphas.shape, betas.shape)
    assert k_slots >= 1, "pad the slot axis to K >= 1 (zero P blocks)"
    assert num_rounds >= 1, "schedule must cover >= 1 round"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,   # nbr_idx, self_idx, nbr_mask, alphas, betas
        grid=(num_rounds, j_nodes),
        in_specs=[
            pl.BlockSpec((t_rows, d_feat), lambda r, j, *_: (0, 0)),  # θ0
            pl.BlockSpec((j_rows, d_feat), lambda r, j, *_: (0, 0)),  # Δ0
            pl.BlockSpec((1, d_feat, d_feat), lambda r, j, *_: (j, 0, 0)),
            _node_rows(dy, d_feat),
            pl.BlockSpec((1, d_feat, d_feat), lambda r, j, *_: (j, 0, 0)),
            pl.BlockSpec((1, k_slots, d_feat, d_feat),
                         lambda r, j, *_: (j, 0, 0, 0)),
        ],
        out_specs=(
            _node_rows(dy, d_feat),                                   # θ
            _node_rows(dy, d_feat),                                   # Δ
        ) + ((_round_row(j_nodes),) if trace else ()),                # res
        scratch_shapes=[
            pltpu.VMEM((t_rows, d_feat), theta.dtype),   # even-round table
            pltpu.VMEM((t_rows, d_feat), theta.dtype),   # odd-round table
            pltpu.VMEM((j_rows, d_feat), theta.dtype),   # Δ table
        ],
    )
    flops_per_node = 2 * (2 + k_slots) * d_feat * d_feat * dy
    rows = jax.ShapeDtypeStruct((j_nodes, dy, d_feat), theta.dtype)
    return pl.pallas_call(
        functools.partial(_dekrr_cheb_solve_kernel, trace=trace),
        grid_spec=grid_spec,
        out_shape=(rows, rows) + ((
            jax.ShapeDtypeStruct((num_rounds, 1, j_nodes), theta.dtype),
        ) if trace else ()),
        cost_estimate=pl.CostEstimate(
            flops=num_rounds * j_nodes * flops_per_node,
            bytes_accessed=((t_rows + j_rows) * d_feat
                            + num_rounds * j_nodes
                            * ((3 + k_slots) * d_feat * d_feat
                               + dy * d_feat)
                            ) * theta.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(nbr_idx, self_idx, nbr_mask, alphas, betas, theta, delta,
      g, d, s, p)


@functools.partial(jax.jit,
                   static_argnames=("num_rounds", "dy", "interpret"))
def dekrr_solve_reference(g, d, s, p, theta, nbr_idx, self_idx, nbr_mask,
                          *, num_rounds: int, dy: int = 1,
                          interpret: bool = False):
    """Pure-jnp oracle with the raw kernel's exact contract: scan the
    single-round oracle, scattering each round's new rows back into the
    θ table at `self_idx` (rows owned by no node stay at θ0) — what
    `tests/test_kernels_dekrr_solve.py` pins the kernel against before
    any repro.dist plumbing is involved."""
    del interpret
    if dy == 1:
        rows = self_idx
    else:
        rows = (self_idx[:, None] * dy + jnp.arange(dy)).reshape(-1)

    def one_round(table, _):
        new = dekrr_step_reference(g, d, s, p, table, nbr_idx, self_idx,
                                   nbr_mask, dy=dy)
        return table.at[rows].set(new), None

    table, _ = jax.lax.scan(one_round, theta, None, length=num_rounds)
    return table[rows]
