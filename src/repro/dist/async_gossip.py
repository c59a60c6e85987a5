"""Asynchronous gossip DeKRR runtime — packed batched and SPMD layers.

`repro.core.async_gossip` defines the semantics (randomized activation,
per-edge staleness buffers, COKE communication censoring) and holds the
ragged ground-truth solver; this module is the production counterpart on
the packed [J, D_max] layout, in the same two shapes as the synchronous
runtime it extends:

1. **Batched single-host execution** (`async_step_batched` /
   `async_solve_batched`): the async round over all nodes at once. The
   Eq. 19 arithmetic routes through `repro.dist.step_batched` with the
   two async extras it grew for this runtime — ``active`` (inactive nodes
   pass θ through untouched; jnp.where on the XLA path, the
   activation-masked `repro.kernels.dekrr_step` variant on the Pallas
   paths) and ``nbr_theta`` (the [J, K, D_max] staleness buffers instead
   of a fresh ``theta[nbr_idx]`` gather). ``backend="pallas_fused"`` runs
   the whole precomputed schedule — [R, J] activation table + [R] censor
   thresholds, scalar-prefetched like the slot tables — through the fused
   async chain kernel (`repro.kernels.ops.dekrr_async_solve`): one
   pallas_call per ``chunk_rounds`` chunk (default: one for the whole
   solve), bit-for-bit the scanned per-round masked kernel. Only one
   accounting mode keeps the per-round path on "pallas_fused":
   ``tol > 0`` (the per-round convergence freeze is host-orchestrated).
   ``return_stats=True`` and ``return_trace=True`` stay fused — the
   chain kernel emits per-(round, node) residual/broadcast trace blocks
   in the same dispatch, and the wire counts (broadcasts, deliveries,
   bytes) are derived from them in plain XLA (`repro.obs` convergence
   traces; this fixed a silent fused→per-round fallback that older
   ``return_stats=True`` calls paid for).

2. **SPMD nodes-on-devices execution** (`make_async_spmd_solver`): one
   node per device, same mesh/mode contract as `make_spmd_solver`. The
   activation masks are precomputed from the shared PRNG key and passed in
   *replicated*, so every device samples the identical schedule without
   coordination and the ppermute/all_gather exchanges stay collective-safe
   — every round runs the dense collective (a lock-step simulation of the
   asynchronous protocol), and the masks gate what lands in the buffers,
   not whether the collective runs. Devices exchange their post-censoring
   ``sent`` vectors: under "bernoulli" gossip a receive buffer always
   equals the sender's last-broadcast θ, so overwriting it with the
   exchanged ``sent`` every round is value-identical to conditional
   delivery and needs no flag traffic; "edge" gossip delivers along the
   sampled edge only, so the broadcast flag rides along as a 1-element
   ppermute/all_gather.

With ``AsyncGossipConfig()`` defaults (prob = 1, bernoulli, no censoring)
every layer reproduces the synchronous runtime bit-for-bit on its own
backend — pinned, along with the cross-layer rtol-1e-9 conformance matrix
over {circulant, star, ER, complete, J=1} × p × censoring, by
`tests/test_async_gossip.py`.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec

from repro.analysis.vmem import check_index_table
from repro.core.async_gossip import (AsyncGossipConfig, activation_masks,
                                     censor_schedule, edges_from_slot_table)
from repro.dist.dekrr_spmd import (PackedProblem, _check_backend,
                                   _check_spmd_problem, _make_exchange,
                                   _node_step, _MODES, _PALLAS_BACKENDS,
                                   shard_map, step_batched)
from repro.obs.trace import AsyncSolveTrace

__all__ = [
    "AsyncGossipState",
    "AsyncGossipStats",
    "AsyncRoundInfo",
    "async_solve_batched",
    "async_step_batched",
    "init_async_state",
    "make_async_spmd_solver",
]

# Default tol-check chunking for the async solve: the per-round freeze
# makes rounds-run independent of the chunk size, so the chunk only sets
# how much work one while_loop iteration dispatches.
_ASYNC_CHUNK_DEFAULT = 16
# The fused chain scalar-prefetches its [R, J] activation table into SMEM:
# 1 MiB per TPU v5e core, each row padded to 128 int32 lanes. One dispatch
# takes at most the rounds whose table fills half of it (1024 rounds for
# J <= 128); longer schedules run in chunks, which is bit-invariant.
_SMEM_TABLE_BYTES = 512 * 1024


def _max_fused_rounds(j_nodes: int) -> int:
    lanes = -(-j_nodes // 128) * 128
    return max(1, _SMEM_TABLE_BYTES // (4 * lanes))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AsyncGossipState:
    """Per-round state of the packed async gossip iteration.

    theta:   [J, D_max]    current iterates (padding exactly zero).
    sent:    [J, D_max]    last θ each node actually broadcast (the COKE
                           censor reference).
    buffers: [J, K, D_max] per-edge receive buffers: buffers[j, k] is the
                           last θ node j *received* from the neighbor in
                           slot k — under "edge" gossip this can be staler
                           than that neighbor's own ``sent``.

    Multi-output packings append a trailing Dy axis to all three
    (θ/sent [J, D_max, Dy], buffers [J, K, D_max, Dy]); the censor
    decision then takes max|Δθ| over features AND outputs, so one
    broadcast carries all Dy columns or none.
    """

    theta: jax.Array
    sent: jax.Array
    buffers: jax.Array

    def tree_flatten(self):
        return (self.theta, self.sent, self.buffers), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


class AsyncRoundInfo(NamedTuple):
    """What one async round put on the wire (for stats and property tests).

    bcast:    [J] bool — nodes that transmitted this round (active and
              uncensored).
    received: [J, K] bool — receive-buffer slots refreshed by a fresh
              broadcast this round.
    """

    bcast: jax.Array
    received: jax.Array


class AsyncGossipStats(NamedTuple):
    """Cumulative communication accounting of an async solve (int32)."""

    rounds: jax.Array
    broadcasts: jax.Array
    deliveries: jax.Array


def init_async_state(packed: PackedProblem,
                     theta0: jax.Array | None = None) -> AsyncGossipState:
    """Round-0 state: every buffer holds its neighbor's θ0 and every node
    'sent' θ0 — exactly the synchronous iteration's view of round 0."""
    if theta0 is None:
        theta0 = jnp.zeros_like(packed.d)
    return AsyncGossipState(theta=theta0, sent=theta0,
                            buffers=theta0[packed.nbr_idx])


def _packed_edges(packed: PackedProblem) -> np.ndarray:
    """Canonical edge list for `gossip="edge"` sampling, derived host-side
    from the slot table (bit-identical to `repro.core.edge_list` on the
    originating topology — tested). Endpoints are bounds-checked against
    [0, J): the edge draw indexes the activation mask with them, and the
    mask feeds the scalar-prefetched activation table of the Pallas round
    kernel (no hardware bounds check there)."""
    edges = edges_from_slot_table(np.asarray(packed.nbr_idx),
                                  np.asarray(packed.nbr_mask))
    check_index_table("edges", edges, packed.num_nodes)
    return edges


def _check_mask_table(name: str, masks, num_rounds: int,
                      num_nodes: int) -> None:
    """Activation-mask schedules must be exactly [R, J] (or [J] for a
    single round): the Pallas round kernel scalar-prefetches the per-round
    [J] row, and a mis-shaped table would be silently broadcast or
    truncated by downstream indexing instead of erroring."""
    shape = tuple(masks.shape)
    want = (num_rounds, num_nodes) if num_rounds >= 0 else (num_nodes,)
    if shape != want:
        raise ValueError(
            f"{name}: activation-mask table has shape {list(shape)}, "
            f"expected {list(want)} — one row per round, one column per "
            f"node (the masked round kernel scalar-prefetches rows of "
            f"this table)")


def _async_round(packed: PackedProblem, state: AsyncGossipState,
                 active: jax.Array, threshold: jax.Array, *,
                 gossip: str, censored: bool,
                 backend: str) -> tuple[AsyncGossipState, AsyncRoundInfo]:
    """One async gossip round in the order every layer shares: update
    (against the staleness buffers) → censor → deliver."""
    new = step_batched(packed, state.theta, backend=backend,
                       active=active, nbr_theta=state.buffers)
    if censored:
        # per-node max|Δθ| over features AND (for multi-output) outputs
        delta = jnp.max(jnp.abs(new - state.sent),
                        axis=tuple(range(1, new.ndim)))      # [J]
        bcast = active & (delta > threshold)
    else:
        bcast = active
    live = packed.nbr_mask != 0
    received = live & bcast[packed.nbr_idx]                  # [J, K]
    if gossip == "edge":
        received = received & active[:, None]  # pairwise: endpoint only
    sent = jnp.where(jnp.reshape(bcast, (-1,) + (1,) * (new.ndim - 1)),
                     new, state.sent)
    buffers = jnp.where(
        jnp.reshape(received, received.shape + (1,) * (new.ndim - 1)),
        new[packed.nbr_idx], state.buffers)
    return (AsyncGossipState(theta=new, sent=sent, buffers=buffers),
            AsyncRoundInfo(bcast=bcast, received=received))


@partial(jax.jit, static_argnames=("gossip", "censored", "backend"))
def async_step_batched(packed: PackedProblem, state: AsyncGossipState,
                       active: jax.Array, threshold: jax.Array = 0.0, *,
                       gossip: str = "bernoulli", censored: bool = False,
                       backend: str = "xla"
                       ) -> tuple[AsyncGossipState, AsyncRoundInfo]:
    """One async gossip round over all nodes, from an explicit activation
    mask ([J] bool) and censor threshold (scalar; ignored unless
    ``censored``). The building block `async_solve_batched` scans — public
    so tests can drive rounds one at a time and inspect the state/wire
    traffic between them.
    """
    _check_backend(backend)
    _check_mask_table("async_step_batched", active, -1, packed.num_nodes)
    return _async_round(packed, state, active,
                        jnp.asarray(threshold, packed.d.dtype),
                        gossip=gossip, censored=censored, backend=backend)


def _count(mask: jax.Array) -> jax.Array:
    return jnp.sum(mask, dtype=jnp.int32)


def _wire_series(packed: PackedProblem, masks: jax.Array,
                 bcast_rj: jax.Array, *, gossip: str):
    """Per-round wire counts from per-(round, node) broadcast flags, in
    plain XLA (no extra kernel dispatch): [R] active / broadcasts /
    deliveries / bytes. Reproduces `_async_round`'s delivery rule —
    ``received = live & bcast[nbr_idx]`` (edge gossip additionally gates
    on the *receiver* being an endpoint) — so summing the series matches
    the per-round path's `AsyncGossipStats` exactly."""
    bc = bcast_rj != 0                                        # [R, J]
    active = jnp.sum(masks != 0, axis=1, dtype=jnp.int32)
    broadcasts = jnp.sum(bc, axis=1, dtype=jnp.int32)
    live = packed.nbr_mask != 0                               # [J, K]
    recv = live[None] & bc[:, packed.nbr_idx]                 # [R, J, K]
    if gossip == "edge":
        recv = recv & (masks != 0)[:, :, None]
    deliveries = jnp.sum(recv, axis=(1, 2), dtype=jnp.int32)
    per_bcast = (packed.max_features * packed.num_outputs
                 * np.dtype(packed.d.dtype).itemsize)
    return (active, broadcasts, deliveries,
            broadcasts * jnp.asarray(per_bcast, jnp.int32))


def _async_solve_fused(packed, state, masks, thresholds, *, gossip,
                       censored, chunk_rounds, trace=False):
    """tol = 0 fused chain: the whole precomputed schedule (or each
    `chunk_rounds` slice of it) runs as one async-chain pallas_call. The
    kernel returns the full `AsyncGossipState`, so chunk boundaries chain
    bit-exactly and the result is chunk-size bit-invariant. With
    ``trace`` the same dispatches also fill the per-(round, node)
    residual ([R, J] float) and broadcast-flag ([R, J] int32) blocks —
    returned alongside θ, concatenated across chunks. Chunks never
    exceed `_max_fused_rounds`, whatever `chunk_rounds` asks for."""
    from repro.kernels.ops import dekrr_async_solve

    num_iters = int(masks.shape[0])
    j_nodes = int(masks.shape[1])
    chunk_rounds = min(chunk_rounds or num_iters, _max_fused_rounds(j_nodes))

    def call(st, mask_tab, thr_tab):
        outs = dekrr_async_solve(
            packed.g, packed.d, packed.s, packed.p, st.theta, st.sent,
            st.buffers, packed.nbr_idx, packed.nbr_mask, mask_tab,
            thr_tab, gossip=gossip, censored=censored, trace=trace)
        st = AsyncGossipState(theta=outs[0], sent=outs[1], buffers=outs[2])
        return st, (outs[3], outs[4]) if trace else None

    if chunk_rounds >= num_iters:
        state, tr = call(state, masks, thresholds)
        return (state.theta,) + tr if trace else state.theta

    n_full, rem = divmod(num_iters, chunk_rounds)
    cut = n_full * chunk_rounds

    def chunk_fn(st, xs):
        mask_tab, thr_tab = xs
        return call(st, mask_tab, thr_tab)

    state, trs = lax.scan(
        chunk_fn, state,
        (masks[:cut].reshape(n_full, chunk_rounds, masks.shape[1]),
         thresholds[:cut].reshape(n_full, chunk_rounds)))
    tr_rem = None
    if rem:
        state, tr_rem = call(state, masks[cut:], thresholds[cut:])
    if not trace:
        return state.theta
    res, bc = (t.reshape(-1, j_nodes) for t in trs)
    if tr_rem is not None:
        res = jnp.concatenate([res, tr_rem[0]])
        bc = jnp.concatenate([bc, tr_rem[1]])
    return state.theta, res, bc


@partial(jax.jit, static_argnames=("num_iters", "gossip", "censored",
                                   "backend", "tol", "chunk_rounds",
                                   "return_rounds", "return_stats",
                                   "return_trace"))
def _async_solve_impl(packed, masks, thresholds, theta0, *, num_iters,
                      gossip, censored, backend, tol, chunk_rounds,
                      return_rounds, return_stats, return_trace):
    state0 = init_async_state(packed, theta0)
    zero = jnp.asarray(0, jnp.int32)
    need_wire = return_stats or return_trace

    def finish(theta, rounds, nb, nd, series):
        out = (theta,)
        if return_rounds:
            out = out + (rounds,)
        if return_stats:
            out = out + (AsyncGossipStats(rounds=rounds, broadcasts=nb,
                                          deliveries=nd),)
        if return_trace:
            residuals, active, bcasts, delivs, wire_bytes = series
            out = out + (AsyncSolveTrace(residuals=residuals, active=active,
                                         broadcasts=bcasts,
                                         deliveries=delivs,
                                         bytes=wire_bytes),)
        return out[0] if len(out) == 1 else out

    if tol == 0.0 and backend == "pallas_fused":
        # Fused async chain: the whole schedule (or each chunk_rounds
        # slice) is one pallas_call. Only tol > 0 keeps the per-round
        # path (host-orchestrated convergence freeze): stats and traces
        # come from the kernel's own [R, J] residual/broadcast trace
        # blocks, with the wire series derived in plain XLA.
        rounds = jnp.asarray(num_iters, jnp.int32)
        if not need_wire:
            theta = _async_solve_fused(packed, state0, masks, thresholds,
                                       gossip=gossip, censored=censored,
                                       chunk_rounds=chunk_rounds)
            return finish(theta, rounds, None, None, None)
        theta, res, bc = _async_solve_fused(
            packed, state0, masks, thresholds, gossip=gossip,
            censored=censored, chunk_rounds=chunk_rounds, trace=True)
        active, bcasts, delivs, wire_bytes = _wire_series(
            packed, masks, bc, gossip=gossip)
        residuals = jnp.max(res, axis=1) if num_iters else \
            jnp.zeros((0,), theta.dtype)
        return finish(theta, rounds, jnp.sum(bcasts), jnp.sum(delivs),
                      (residuals, active, bcasts, delivs, wire_bytes))

    if tol == 0.0:
        def round_fn(carry, xs):
            state, nb, nd = carry
            mask_r, thr_r = xs
            new_state, info = _async_round(packed, state, mask_r, thr_r,
                                           gossip=gossip, censored=censored,
                                           backend=backend)
            ys = None
            if return_trace:
                ys = (jnp.max(jnp.abs(new_state.theta - state.theta)),
                      info.bcast.astype(jnp.int32))
            return (new_state, nb + _count(info.bcast),
                    nd + _count(info.received)), ys

        (state, nb, nd), ys = lax.scan(round_fn, (state0, zero, zero),
                                       (masks, thresholds))
        rounds = jnp.asarray(num_iters, jnp.int32)
        series = None
        if return_trace:
            residuals, bc = ys
            series = (residuals,) + _wire_series(packed, masks, bc,
                                                 gossip=gossip)
        return finish(state.theta, rounds, nb, nd, series)
    else:
        # tol > 0: per-round convergence freeze inside chunked execution.
        # Convergence is evaluated after EVERY round (not at chunk
        # boundaries) and a converged solve passes subsequent rounds
        # through unchanged, so rounds-run and θ are independent of
        # chunk_rounds — the chunk only sets how much work one while_loop
        # iteration dispatches (regression-tested).
        chunk = chunk_rounds if chunk_rounds is not None \
            else _ASYNC_CHUNK_DEFAULT
        chunk = min(chunk, max(num_iters, 1))
        n_chunks = -(-num_iters // chunk)
        pad = n_chunks * chunk - num_iters
        masks_p = jnp.pad(masks, ((0, pad), (0, 0)))
        thresholds_p = jnp.pad(thresholds, (0, pad))
        # Preallocated [num_iters] trace buffers, written in place at the
        # absolute round index inside the existing scan: frozen (and
        # never-run) rounds keep their 0, which is what makes tol-path
        # traces chunk-invariant. mode="drop" ignores the padded rounds'
        # out-of-range indices.
        buf0 = (jnp.zeros((num_iters,), state0.theta.dtype),
                jnp.zeros((num_iters,), jnp.int32),
                jnp.zeros((num_iters,), jnp.int32)) if return_trace else ()

        def round_fn(carry, xs):
            state, rounds, converged, nb, nd = carry[:5]
            mask_r, thr_r, r_abs = xs
            new_state, info = _async_round(packed, state, mask_r, thr_r,
                                           gossip=gossip,
                                           censored=censored,
                                           backend=backend)
            delta = jnp.max(jnp.abs(new_state.theta - state.theta))
            take = jnp.logical_not(converged) & (r_abs < num_iters)
            state = jax.tree_util.tree_map(
                lambda a, b: jnp.where(take, a, b), new_state, state)
            rounds = rounds + take.astype(jnp.int32)
            b = jnp.where(take, _count(info.bcast), 0)
            dv = jnp.where(take, _count(info.received), 0)
            nb = nb + b
            nd = nd + dv
            # A round the Bernoulli draw left all-silent has Δθ ≡ 0 by
            # construction — that is the schedule idling, not the
            # iteration converging, so it must not latch the stop.
            converged = converged | (take & jnp.any(mask_r)
                                     & (delta < tol))
            out = (state, rounds, converged, nb, nd)
            if return_trace:
                rbuf, bbuf, dbuf = carry[5:]
                out = out + (
                    rbuf.at[r_abs].set(jnp.where(take, delta, 0.0),
                                       mode="drop"),
                    bbuf.at[r_abs].set(b, mode="drop"),
                    dbuf.at[r_abs].set(dv, mode="drop"))
            return out, None

        def cond_fn(carry):
            converged, chunk_idx = carry[2], carry[-1]
            return jnp.logical_not(converged) & (chunk_idx < n_chunks)

        def body_fn(carry):
            chunk_idx = carry[-1]
            start = chunk_idx * chunk
            xs = (lax.dynamic_slice_in_dim(masks_p, start, chunk, 0),
                  lax.dynamic_slice_in_dim(thresholds_p, start, chunk, 0),
                  start + jnp.arange(chunk))
            carry, _ = lax.scan(round_fn, carry[:-1], xs)
            return carry + (chunk_idx + 1,)

        carry = lax.while_loop(
            cond_fn, body_fn,
            (state0, zero, jnp.asarray(False), zero, zero) + buf0 + (zero,))
        state, rounds, _, nb, nd = carry[:5]
        series = None
        if return_trace:
            residuals, bc_rounds, dv_rounds = carry[5:8]
            # broadcast-flag [R, J] is not materialized on this path (the
            # counts are), so active/bytes come from the schedule and the
            # per-round broadcast counts; frozen rounds record 0 across
            # every field.
            ran = (jnp.arange(num_iters, dtype=jnp.int32)
                   < rounds).astype(jnp.int32)
            active = jnp.sum(masks != 0, axis=1, dtype=jnp.int32) * ran
            per_bcast = (packed.max_features * packed.num_outputs
                         * np.dtype(packed.d.dtype).itemsize)
            series = (residuals, active, bc_rounds, dv_rounds,
                      bc_rounds * jnp.asarray(per_bcast, jnp.int32))
        return finish(state.theta, rounds, nb, nd, series)


def async_solve_batched(packed: PackedProblem, num_iters: int,
                        key: jax.Array, *,
                        config: AsyncGossipConfig = AsyncGossipConfig(),
                        theta0: jax.Array | None = None,
                        backend: str = "xla", tol: float = 0.0,
                        chunk_rounds: int | None = None,
                        return_rounds: bool = False,
                        return_stats: bool = False,
                        return_trace: bool = False):
    """Run up to `num_iters` async gossip rounds from θ = 0 (or theta0).

    The whole activation/censor schedule is precomputed from `key` via the
    shared `repro.core.async_gossip` helpers (round r uses
    ``fold_in(key, r)``), then the solve runs on the chosen ``backend``:
    "xla" and "pallas" scan the per-round (activation-masked) round;
    "pallas_fused" feeds the schedule through scalar prefetch and runs
    ALL rounds in one async-chain pallas_call — or one per
    ``chunk_rounds`` chunk, bit-invariant to the chunking — falling back
    to the scanned per-round masked kernel only for the one accounting
    mode the kernel cannot host (``tol > 0``; see module docstring —
    ``return_stats``/``return_trace`` used to force this fallback too,
    but now read the fused kernel's own trace blocks).

    ``tol > 0`` enables early stopping on max|Δθ| < tol, evaluated after
    every round on device — except rounds the activation draw left
    all-silent, whose Δθ ≡ 0 says nothing about convergence (a
    non-trivial hazard at small p·J). Once a round converges, later
    rounds pass through unchanged, so the reported round count and θ are
    independent of ``chunk_rounds`` (which only sets the while_loop
    dispatch granularity). ``return_rounds`` appends the rounds-run int32
    scalar; ``return_stats`` appends an `AsyncGossipStats` with the
    cumulative broadcast/delivery counts for communication accounting;
    ``return_trace`` appends a `repro.obs.trace.AsyncSolveTrace` of
    per-round [num_iters] device buffers — max|Δθ| residuals plus the
    scheduled/broadcast/delivery/bytes wire series (frozen and never-run
    rounds record 0; sums reproduce the stats exactly). Appended outputs
    keep that order: ``(theta[, rounds][, stats][, trace])``. Traces are
    filled inside the existing scan/while/kernel round structure — no
    host callback, no extra kernel dispatch (pinned by
    ``tests/test_obs.py``).

    With ``config.is_synchronous`` this reproduces
    ``solve_batched(packed, num_iters, backend=backend)`` bit-for-bit.
    """
    _check_backend(backend)
    if tol < 0:
        raise ValueError(f"tol must be >= 0, got {tol}")
    if chunk_rounds is not None and chunk_rounds < 1:
        raise ValueError(f"chunk_rounds must be >= 1, got {chunk_rounds}")
    num_iters = int(num_iters)
    edges = _packed_edges(packed) if config.gossip == "edge" else None
    masks = activation_masks(key, num_iters, packed.num_nodes,
                             prob=config.prob, gossip=config.gossip,
                             edges=edges)
    _check_mask_table("async_solve_batched", masks, num_iters,
                      packed.num_nodes)
    thresholds = censor_schedule(config.censor_tau, config.censor_decay,
                                 num_iters, dtype=packed.d.dtype)
    return _async_solve_impl(
        packed, masks, thresholds, theta0, num_iters=num_iters,
        gossip=config.gossip, censored=config.censored, backend=backend,
        tol=float(tol), chunk_rounds=chunk_rounds,
        return_rounds=return_rounds, return_stats=return_stats,
        return_trace=return_trace)


# --------------------------------------------------------------------------
# SPMD nodes-on-devices async runtime
# --------------------------------------------------------------------------
def make_async_spmd_solver(mesh: Mesh, axis_name: str,
                           mode: str = "ppermute", backend: str = "xla"):
    """Build ``run(packed, num_iters, key, config) -> [J, D_max]`` on a
    1-D node mesh — the async counterpart of `make_spmd_solver`.

    Same placement contract (device index along `axis_name` IS the node
    id) and the same exchange modes. The full [R, J] activation-mask
    schedule and [R] censor thresholds are sampled host-side from the
    shared `key` and enter the shard_map *replicated*, so every device
    walks the identical schedule and the per-slot ppermute ring shifts /
    all_gather stay collective-safe: the dense collective runs every
    round, and the masks decide what lands in the staleness buffers.

    Per round each device exchanges its post-censoring ``sent`` vector.
    Under "bernoulli" gossip that alone reproduces conditional delivery
    (a buffer always equals the sender's last broadcast, so the overwrite
    is value-identical — no flag traffic); under "edge" gossip the
    broadcast flag travels with the payload as a 1-element exchange and
    gates delivery to the sampled edge. ``backend`` picks the per-device
    arithmetic: "xla" runs `_node_step` + jnp.where, "pallas"/
    "pallas_fused" run the activation-masked round kernel on the local
    ``[own θ; buffers]`` table.

    With ``config.is_synchronous`` the returned runner reproduces
    ``make_spmd_solver(mesh, axis_name, mode, backend)`` bit-for-bit.

    The returned runner is ``run(packed, num_iters, key, config=...,
    theta0=None, tol=0.0, return_rounds=False)``: ``theta0`` warm-starts
    the iteration exactly like `init_async_state(packed, theta0)` (own θ,
    censor reference, and staleness buffers all seeded from it — the
    buffers via one pre-scan exchange); ``tol > 0`` enables the same
    per-round early stop as `async_solve_batched` — a fused `lax.pmax`
    of the per-device max|Δθ| gives every device the network-wide delta,
    so the per-device while_loops agree on the trip count and exit
    together after the converging round (a genuine stop: no further
    compute or exchange runs, unlike the batched solve's chunk-internal
    freeze), and all-silent rounds never latch the stop (their Δθ ≡ 0
    is the schedule idling, not convergence); θ and the round count
    match the batched async solve exactly. ``return_rounds=True``
    appends the rounds-run int32 scalar.

    ``return_trace=True`` appends a `repro.obs.trace.AsyncSolveTrace`
    with NO extra collective: each device records its LOCAL per-round
    max|Δθ| and its own broadcast flag into scan outputs / while-loop
    carry buffers, and the network-wide residual series (max over the
    device axis) plus the wire series (broadcasts, deliveries from the
    slot tables, bytes) are reduced *outside* the shard_map in plain
    XLA — matching the batched async trace at rtol 1e-9 and its wire
    counts exactly. Appended outputs keep the order
    ``(theta[, rounds][, trace])``.
    """
    if mode not in _MODES:
        raise ValueError(f"mode must be one of {_MODES}, got {mode!r}")
    _check_backend(backend)
    if axis_name not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis_name!r}: {mesh.shape}")

    spec = PartitionSpec(axis_name)
    rep = PartitionSpec()

    @partial(jax.jit, static_argnames=("offsets", "gossip", "censored",
                                       "tol", "return_trace"))
    def _run(g, d, s, p, nbr_idx, nbr_mask, masks, thresholds, theta0, *,
             offsets, gossip, censored, tol, return_trace=False):
        j_nodes = d.shape[0]
        k_slots = p.shape[1]

        def node_program(g, d, s, p, nbr_idx, nbr_mask, masks, thresholds,
                         theta0):
            me = lax.axis_index(axis_name)
            live = nbr_mask[0] != 0                          # [K]
            # the sync solver's θ exchange, verbatim (shared helper)
            exchange = _make_exchange(mode, axis_name, j_nodes, offsets,
                                      nbr_idx)

            def one_round(theta, sent, buffers, mask_r, thr_r):
                active = mask_r[me]
                if backend in _PALLAS_BACKENDS:
                    from repro.kernels.ops import dekrr_step

                    # local θ table: row 0 = own θ, rows 1…K = buffers
                    table = jnp.concatenate([theta, buffers], axis=0)
                    local_idx = jnp.arange(
                        1, k_slots + 1, dtype=jnp.int32)[None]
                    new = dekrr_step(
                        g, d, s, p, table, local_idx,
                        jnp.zeros((1,), jnp.int32), nbr_mask,
                        jnp.reshape(active, (1,)))
                else:
                    new = _node_step(g[0], d[0], s[0], p[0], theta[0],
                                     buffers, nbr_mask[0])[None]
                    new = jnp.where(active, new, theta)
                if censored:
                    delta = jnp.max(jnp.abs(new - sent))
                    bcast = active & (delta > thr_r)
                else:
                    bcast = active
                sent_new = jnp.where(bcast, new, sent)
                payload = exchange(sent_new)                 # [K, D]
                if gossip == "edge":
                    flag = exchange(jnp.reshape(bcast, (1, 1))
                                    .astype(d.dtype))[:, 0] != 0
                    gate = active & mask_r[nbr_idx[0]] & flag & live
                else:
                    gate = live
                buffers = jnp.where(
                    jnp.reshape(gate, (-1,) + (1,) * (payload.ndim - 1)),
                    payload, buffers)
                return new, sent_new, buffers, bcast

            # round-0 staleness view: every buffer holds its neighbor's
            # θ0 (init_async_state semantics — masked slots carry the
            # node's own θ0, exactly like theta0[nbr_idx]), fetched with
            # one pre-scan exchange; exact zeros on the cold start
            buffers0 = exchange(theta0)

            if tol == 0.0:
                def round_fn(carry, xs):
                    theta, sent, buffers = carry
                    mask_r, thr_r = xs
                    new, sent_new, buf_new, bcast = one_round(
                        theta, sent, buffers, mask_r, thr_r)
                    # LOCAL per-round trace: own max|Δθ| + own broadcast
                    # flag — no collective; reduced outside the shard_map
                    ys = (jnp.max(jnp.abs(new - theta)),
                          bcast.astype(jnp.int32)) if return_trace \
                        else None
                    return (new, sent_new, buf_new), ys

                (theta, _, _), ys = lax.scan(
                    round_fn, (theta0, theta0, buffers0),
                    (masks, thresholds))
                rounds = jnp.full((1,), masks.shape[0], jnp.int32)
                if return_trace:
                    return theta, rounds, ys[0][None], ys[1][None]
                return theta, rounds

            # genuine early exit (matches the sync SPMD solver): the
            # pmax-fused delta keeps the per-device while_loop trip
            # counts identical, so the in-body collectives stay matched
            # and a converged solve stops paying for the budget's tail.
            def cond_fn(carry):
                converged, rounds = carry[3], carry[4]
                return jnp.logical_not(converged) & (rounds < masks.shape[0])

            def body_fn(carry):
                theta, sent, buffers, converged, rounds = carry[:5]
                mask_r = lax.dynamic_index_in_dim(masks, rounds, 0,
                                                  keepdims=False)
                thr_r = lax.dynamic_index_in_dim(thresholds, rounds, 0,
                                                 keepdims=False)
                new, sent_new, buf_new, bcast = one_round(
                    theta, sent, buffers, mask_r, thr_r)
                delta_local = jnp.max(jnp.abs(new - theta))
                delta = lax.pmax(delta_local, axis_name)
                # all-silent rounds have Δθ ≡ 0 by construction — the
                # schedule idling, not convergence (same latch rule as
                # the batched async solve)
                converged = converged | (jnp.any(mask_r) & (delta < tol))
                out = (new, sent_new, buf_new, converged, rounds + 1)
                if return_trace:
                    rbuf, bbuf = carry[5:]
                    out = out + (rbuf.at[rounds].set(delta_local),
                                 bbuf.at[rounds].set(
                                     bcast.astype(jnp.int32)))
                return out

            num_iters = masks.shape[0]
            buf0 = (jnp.zeros((num_iters,), theta0.dtype),
                    jnp.zeros((num_iters,), jnp.int32)) \
                if return_trace else ()
            carry = lax.while_loop(
                cond_fn, body_fn,
                (theta0, theta0, buffers0, jnp.asarray(False),
                 jnp.asarray(0, jnp.int32)) + buf0)
            theta, rounds = carry[0], jnp.reshape(carry[4], (1,))
            if return_trace:
                return theta, rounds, carry[5][None], carry[6][None]
            return theta, rounds

        out_spec = (spec, spec, spec, spec) if return_trace \
            else (spec, spec)
        sharded = shard_map(
            node_program, mesh=mesh,
            in_specs=(spec, spec, spec, spec, spec, spec, rep, rep, spec),
            out_specs=out_spec,
            # Same rule as `make_spmd_solver`: the varying-manual-axes
            # check needs a `vma` on pallas_call outputs and rejects the
            # tol path's while_loop carries; lint J005 covers both paths.
            check_vma=(backend not in _PALLAS_BACKENDS and tol == 0.0),
        )
        return sharded(g, d, s, p, nbr_idx, nbr_mask, masks, thresholds,
                       theta0)

    def run(packed: PackedProblem, num_iters: int, key: jax.Array,
            config: AsyncGossipConfig = AsyncGossipConfig(),
            theta0: jax.Array | None = None, *, tol: float = 0.0,
            return_rounds: bool = False, return_trace: bool = False):
        _check_spmd_problem(packed, mesh, axis_name, mode)
        if tol < 0:
            raise ValueError(f"tol must be >= 0, got {tol}")
        num_iters = int(num_iters)
        edges = _packed_edges(packed) if config.gossip == "edge" else None
        masks = activation_masks(key, num_iters, packed.num_nodes,
                                 prob=config.prob, gossip=config.gossip,
                                 edges=edges)
        _check_mask_table("make_async_spmd_solver", masks, num_iters,
                          packed.num_nodes)
        thresholds = censor_schedule(
            config.censor_tau, config.censor_decay, num_iters,
            dtype=packed.d.dtype)
        if theta0 is None:
            theta0 = jnp.zeros_like(packed.d)
        outs = _run(packed.g, packed.d, packed.s, packed.p,
                    packed.nbr_idx, packed.nbr_mask, masks,
                    thresholds, theta0, offsets=packed.offsets,
                    gossip=config.gossip, censored=config.censored,
                    tol=float(tol), return_trace=return_trace)
        theta, rounds = outs[0], outs[1]
        out = (theta,)
        if return_rounds:
            out = out + (jnp.max(rounds),)
        if return_trace:
            # per-device [J, R] local residuals / broadcast flags →
            # network-wide series, reduced outside the shard_map
            res, bc = outs[2], outs[3]
            active, bcasts, delivs, wire_bytes = _wire_series(
                packed, masks, bc.T, gossip=config.gossip)
            ran = (jnp.arange(num_iters, dtype=jnp.int32)
                   < jnp.max(rounds)).astype(jnp.int32)
            out = out + (AsyncSolveTrace(
                residuals=jnp.max(res, axis=0), active=active * ran,
                broadcasts=bcasts, deliveries=delivs, bytes=wire_bytes),)
        return out[0] if len(out) == 1 else out

    return run
