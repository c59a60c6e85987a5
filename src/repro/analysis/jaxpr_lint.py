"""Jaxpr-level lint of the solver entry points.

Each public solver program (`solve_batched`, `async_solve_batched`, the
SPMD solvers, the `dekrr_step`/`dekrr_solve` ops wrappers, the streaming
ingest fold) is traced to a closed jaxpr on a tiny synthetic problem and
statically verified — no solver numerics run, only tracing. Rules:

  J001  No host callbacks (`pure_callback`/`io_callback`/`debug_callback`)
        inside `while`/`scan` bodies — one device→host sync per iteration
        is exactly the per-round stall PR 3 removed.
  J002  Kernel dispatch counts match the documented `round_dispatches`
        contract (BENCH_solve.json): sync solve {xla: 0, pallas: R,
        pallas_fused: 1}; async {xla: 0, pallas: R, pallas_fused: 1 —
        the [R, J] mask table prefetches into one multi-round kernel};
        the ops wrappers dispatch exactly once; `return_trace=True` /
        `return_stats=True` variants pin the SAME counts (telemetry
        never buys an extra launch). Counts are computed statically with
        `lax.scan` length multipliers; the counter itself lives in
        `repro.obs.dispatch` (re-exported here).
  J003  Every `ppermute` permutation is a bijection over its mesh axis:
        pairs in range, sources and destinations distinct, and full
        coverage (an uncovered receiver silently gets zeros).
  J004  No silent x64→f32 downcasts (`convert_element_type`) inside
        `while`/`scan` bodies — a downcast θ carry would quietly degrade
        the rtol-1e-9 parity contract round over round.
  J005  Under `shard_map(..., check_vma=False)` (which disables JAX's own
        replication checking — the Pallas and tol>0 paths), any
        `while_loop` predicate or `cond` branch index that gates
        collectives must be *provably replicated* across the mesh: a
        device-varying trip count deadlocks the in-body
        ppermute/all_gather (the PR 4 mask-schedule hazard). The issue
        phrases this as "operands entering collectives must be
        replicated"; operand *payloads* are intentionally sharded (that
        is the point of the exchange) — what must be replicated is the
        control deciding whether the collective executes, which is what
        this rule proves via a conservative dataflow analysis
        (`psum`/`pmax`/`pmin`/`all_gather` over the mesh axis produce
        replicated values; `axis_index`/`ppermute` device-varying ones;
        everything else propagates meet-over-inputs).
  V002  Every `pallas_call` in a traced program fits the 16 MiB VMEM
        budget, estimated generically from its BlockSpecs (grid-mapping
        block shapes + VMEM scratch avals) — reported under the vmem
        pass; the closed-form per-kernel formulas live in
        `repro.analysis.vmem` and guard the ops wrappers at call time.

The replication analysis is conservative: it proves replication, it does
not prove divergence — so a J005 finding means "not provably safe".

This module imports jax and must only be imported after the process has
fixed its platform/device-count environment (`repro.analysis.__main__`
sets JAX_PLATFORMS=cpu and a forced host device count before importing
it; tests inherit the tier-1 environment).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.report import Finding
from repro.analysis.vmem import VMEM_BUDGET_BYTES, estimate_blocks
from repro.obs.dispatch import count_pallas_dispatches

__all__ = [
    "EntryPoint", "batched_entry_points", "count_pallas_dispatches",
    "lint_program", "run_pass", "spmd_entry_points", "synthetic_packed",
    "unverified_shard_maps", "walk_eqns",
]

# Rounds used for the dispatch-contract traces (any small R > 1 works; the
# contract is per-round structure, not a particular round count).
ROUNDS = 5
# Mesh size for the SPMD traces — requires
# XLA_FLAGS=--xla_force_host_platform_device_count>=4 (the CLI sets it).
SPMD_NODES = 4

_LOOP_FRAMES = ("scan", "while_body", "while_cond")
_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call",
})
_COLLECTIVES = frozenset({
    "ppermute", "psum", "pmax", "pmin", "all_gather", "all_to_all",
    "reduce_scatter", "pgather",
})
# Collectives whose output is identical on every device when taken over
# the mesh axis (the basis of the replication dataflow analysis).
_REPLICATING = frozenset({"psum", "pmax", "pmin", "all_gather"})


# --------------------------------------------------------------------------
# Generic jaxpr walking
# --------------------------------------------------------------------------
def _is_jaxpr(v) -> bool:
    return type(v).__name__ in ("Jaxpr", "ClosedJaxpr")


def _inner(j):
    """Unwrap ClosedJaxpr → Jaxpr (ClosedJaxpr has .jaxpr + .consts)."""
    return j.jaxpr if hasattr(j, "consts") and hasattr(j, "jaxpr") else j


def _jaxpr_params(value):
    """Yield every jaxpr-valued leaf of one eqn param value."""
    if _is_jaxpr(value):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _jaxpr_params(v)


def _sub_jaxprs(eqn):
    """Yield (jaxpr, frame) for each sub-jaxpr of `eqn`. Frames:
    ("scan", length) | ("while_body"|"while_cond"|"cond_branch", None) |
    ("shard_map", eqn) | ("call", None). pallas_call kernel bodies are not
    descended into (their memory behavior is the vmem pass's job and
    their arithmetic is pinned dynamically by the parity suites)."""
    name = eqn.primitive.name
    p = eqn.params
    if name == "pallas_call":
        return
    if name == "scan":
        yield p["jaxpr"], ("scan", int(p.get("length", 1)))
    elif name == "while":
        yield p["cond_jaxpr"], ("while_cond", None)
        yield p["body_jaxpr"], ("while_body", None)
    elif name == "cond":
        for br in p["branches"]:
            yield br, ("cond_branch", None)
    elif name == "shard_map":
        yield p["jaxpr"], ("shard_map", eqn)
    else:
        for v in p.values():
            for sub in _jaxpr_params(v):
                yield sub, ("call", None)


def walk_eqns(closed):
    """Yield (eqn, frames) over the whole program, depth-first; `frames`
    is the tuple of enclosing frames from `_sub_jaxprs`."""
    def rec(jaxpr, frames):
        for eqn in jaxpr.eqns:
            yield eqn, frames
            for sub, frame in _sub_jaxprs(eqn):
                yield from rec(_inner(sub), frames + (frame,))

    yield from rec(_inner(closed), ())


def _contains_collective(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COLLECTIVES:
            return True
        for sub, _ in _sub_jaxprs(eqn):
            if _contains_collective(_inner(sub)):
                return True
    return False


# --------------------------------------------------------------------------
# J001 — host callbacks inside loop bodies
# --------------------------------------------------------------------------
def check_no_callbacks_in_loops(closed, where: str) -> list[Finding]:
    out = []
    for eqn, frames in walk_eqns(closed):
        if eqn.primitive.name not in _CALLBACK_PRIMS:
            continue
        loops = [f[0] for f in frames if f[0] in _LOOP_FRAMES]
        if loops:
            out.append(Finding(
                "jaxpr", "J001", where,
                f"host callback `{eqn.primitive.name}` inside a "
                f"{loops[-1]} — one device→host round-trip per "
                f"iteration"))
    return out


# --------------------------------------------------------------------------
# J002 — dispatch counting (the counter itself lives in repro.obs.dispatch,
# re-exported above — obs is the lower layer; this pass pins the contract)
# --------------------------------------------------------------------------
def check_dispatch_contract(closed, expected: int | None,
                            where: str) -> list[Finding]:
    if expected is None:
        return []
    count, exact = count_pallas_dispatches(closed)
    if not exact:
        return [Finding(
            "jaxpr", "J002", where,
            f"dispatch count is not statically bounded (pallas_call under "
            f"a while_loop) but the round_dispatches contract pins it to "
            f"{expected}")]
    if count != expected:
        return [Finding(
            "jaxpr", "J002", where,
            f"{count} pallas_call dispatch(es) traced but the "
            f"round_dispatches contract documents {expected}")]
    return []


# --------------------------------------------------------------------------
# J003 — ppermute bijections
# --------------------------------------------------------------------------
def ppermute_perm_errors(perm, axis_size: int) -> list[str]:
    """Pure checker (exposed for the seeded-violation tests): the perm of
    a ring exchange must be a bijection over the full axis."""
    perm = [(int(s), int(d)) for s, d in perm]
    errors = []
    for s, d in perm:
        if not (0 <= s < axis_size and 0 <= d < axis_size):
            errors.append(f"pair ({s}, {d}) outside [0, {axis_size})")
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    if len(set(srcs)) != len(srcs):
        errors.append("duplicate source devices")
    if len(set(dsts)) != len(dsts):
        errors.append("duplicate destination devices "
                      "(two sends to one receiver)")
    if not errors and (set(srcs) != set(range(axis_size))
                       or set(dsts) != set(range(axis_size))):
        errors.append(
            f"perm covers {len(set(srcs))}/{axis_size} devices — "
            f"uncovered receivers silently get zeros")
    return errors


def _axis_sizes(frames) -> dict:
    """axis name → size from the innermost enclosing shard_map mesh."""
    for kind, payload in reversed(frames):
        if kind == "shard_map":
            return dict(payload.params["mesh"].shape)
    return {}


def check_ppermute_bijections(closed, where: str) -> list[Finding]:
    out = []
    for eqn, frames in walk_eqns(closed):
        if eqn.primitive.name != "ppermute":
            continue
        axis_name = eqn.params.get("axis_name")
        if isinstance(axis_name, (tuple, list)):
            axis_name = axis_name[0]
        size = _axis_sizes(frames).get(axis_name)
        if size is None:
            continue  # not under shard_map here — axis size unknowable
        for msg in ppermute_perm_errors(eqn.params["perm"], size):
            out.append(Finding(
                "jaxpr", "J003", where,
                f"ppermute over axis {axis_name!r} (size {size}) is not "
                f"a bijection: {msg}"))
    return out


# --------------------------------------------------------------------------
# J004 — silent x64 downcasts in loop bodies
# --------------------------------------------------------------------------
def check_loop_downcasts(closed, where: str) -> list[Finding]:
    out = []
    for eqn, frames in walk_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        loops = [f[0] for f in frames if f[0] in _LOOP_FRAMES]
        if not loops:
            continue
        src = np.dtype(eqn.invars[0].aval.dtype)
        dst = np.dtype(eqn.params["new_dtype"])
        if src == np.float64 and dst == np.float32:
            out.append(Finding(
                "jaxpr", "J004", where,
                f"silent f64→f32 downcast inside a {loops[-1]} — an x64 "
                f"carry degraded mid-iteration breaks the rtol-1e-9 "
                f"parity contract"))
    return out


# --------------------------------------------------------------------------
# J005 — replication analysis under check_vma=False
# --------------------------------------------------------------------------
def _eqn_axis_names(eqn) -> set:
    names = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(names, (tuple, list)):
        names = (names,)
    return set(names)


def _rep_propagate(jaxpr, in_reps, axes, findings, where):
    """Forward replication dataflow over one (open) jaxpr. Returns the
    outvars' replication. Conservative: proves replication only."""
    rep = {}

    def read(v):
        return True if type(v).__name__ == "Literal" else rep.get(v, False)

    for v in jaxpr.constvars:
        rep[v] = True                 # trace-time constants: same everywhere
    for v, r in zip(jaxpr.invars, in_reps):
        rep[v] = bool(r)
    for eqn in jaxpr.eqns:
        ins = [read(v) for v in eqn.invars]
        outs = _rep_eqn(eqn, ins, axes, findings, where)
        for v, r in zip(eqn.outvars, outs):
            rep[v] = r
    return [read(v) for v in jaxpr.outvars]


def _rep_eqn(eqn, ins, axes, findings, where):
    name = eqn.primitive.name
    p = eqn.params
    n_out = len(eqn.outvars)
    if name == "axis_index":
        return [False]
    if name in ("ppermute", "all_to_all"):
        return [False] * n_out
    if name in _REPLICATING and (axes & _eqn_axis_names(eqn)):
        return [True] * n_out
    if name == "while":
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_consts, body_consts = ins[:cn], ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        body, cond = p["body_jaxpr"], p["cond_jaxpr"]
        for _ in range(len(carry) + 1):         # monotone meet → converges
            outs = _rep_propagate(_inner(body), body_consts + carry,
                                  axes, findings, where)
            new = [a and b for a, b in zip(carry, outs)]
            if new == carry:
                break
            carry = new
        pred = _rep_propagate(_inner(cond), cond_consts + carry,
                              axes, findings, where)[0]
        if not pred and _contains_collective(_inner(body)):
            findings.append(Finding(
                "jaxpr", "J005", where,
                "while_loop predicate is not provably replicated across "
                "the mesh but the body issues collectives — under "
                "check_vma=False devices can disagree on the trip count "
                "and deadlock the exchange"))
        return carry
    if name == "scan":
        nc, ncar = p["num_consts"], p["num_carry"]
        consts, xs = ins[:nc], ins[nc + ncar:]
        carry = list(ins[nc:nc + ncar])
        body = _inner(p["jaxpr"])
        outs = None
        for _ in range(len(carry) + 1):
            outs = _rep_propagate(body, consts + carry + xs,
                                  axes, findings, where)
            new = [a and b for a, b in zip(carry, outs[:ncar])]
            if new == carry:
                break
            carry = new
        ys = outs[ncar:] if outs is not None else []
        return carry + list(ys)
    if name == "cond":
        pred, ops = ins[0], list(ins[1:])
        branch_outs = [
            _rep_propagate(_inner(b), ops, axes, findings, where)
            for b in p["branches"]]
        if not pred and any(_contains_collective(_inner(b))
                            for b in p["branches"]):
            findings.append(Finding(
                "jaxpr", "J005", where,
                "cond branch index is not provably replicated across the "
                "mesh but a branch issues collectives — under "
                "check_vma=False devices can take different branches and "
                "deadlock the exchange"))
        return [pred and all(col) for col in zip(*branch_outs)]
    # Generic call-like eqn (pjit, custom_jvp/vjp, remat, …): recurse when
    # exactly one sub-jaxpr matches the operand arity.
    subs = [s for v in p.values() for s in _jaxpr_params(v)]
    if len(subs) == 1 and len(_inner(subs[0]).invars) == len(ins):
        return _rep_propagate(_inner(subs[0]), ins, axes, findings, where)
    # Default: elementwise-style — replicated iff every input is.
    return [all(ins) if ins else True] * n_out


def _spec_axes(spec) -> set:
    """Mesh axis names a PartitionSpec shards over (empty = replicated)."""
    names = set()
    for entry in spec:
        if entry is None:
            continue
        names.update(entry if isinstance(entry, tuple) else (entry,))
    return names


def unverified_shard_maps(closed) -> list:
    """The `shard_map` eqns traced with ``check_vma=False`` — the ones
    JAX does not check and J005 must analyze."""
    return [eqn for eqn, _frames in walk_eqns(closed)
            if eqn.primitive.name == "shard_map"
            and not eqn.params["check_vma"]]


def check_replication(closed, where: str) -> list[Finding]:
    findings: list[Finding] = []
    for eqn in unverified_shard_maps(closed):
        axes = set(dict(eqn.params["mesh"].shape))
        in_reps = [not (_spec_axes(spec) & axes)
                   for spec in eqn.params["in_specs"]]
        _rep_propagate(_inner(eqn.params["jaxpr"]), in_reps, axes,
                       findings, where)
    # Nested fixpoint iterations can emit duplicates — dedupe, keep order.
    return list(dict.fromkeys(findings))


# --------------------------------------------------------------------------
# V002 — generic VMEM budget from BlockSpecs of traced pallas_calls
# --------------------------------------------------------------------------
def check_traced_vmem(closed, where: str) -> list[Finding]:
    out = []
    for eqn, _frames in walk_eqns(closed):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            continue
        blocks = []
        for bm in getattr(gm, "block_mappings", ()) or ():
            shape = tuple(int(d) for d in bm.block_shape
                          if isinstance(d, int))
            aval = getattr(bm, "block_aval", None)
            dtype = getattr(aval, "dtype", None)
            itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
            blocks.append((shape, itemsize))
        n_scratch = getattr(gm, "num_scratch_operands", 0)
        if n_scratch:
            kernel_invars = _inner(eqn.params["jaxpr"]).invars
            for v in kernel_invars[-n_scratch:]:
                aval = v.aval
                blocks.append((tuple(int(d) for d in aval.shape),
                               np.dtype(aval.dtype).itemsize))
        est = estimate_blocks(f"pallas_call@{where}", blocks)
        if not est.fits:
            out.append(Finding(
                "vmem", "V002", where,
                f"traced pallas_call working set {est.detail} = "
                f"{est.bytes} bytes exceeds the {VMEM_BUDGET_BYTES}-byte "
                f"VMEM budget (single-buffered lower bound)"))
    return out


# --------------------------------------------------------------------------
# Entry-point harness
# --------------------------------------------------------------------------
@dataclasses.dataclass
class EntryPoint:
    """One traceable solver program: `trace()` returns its closed jaxpr;
    `expected_dispatches` pins the J002 contract (None = not pinned, e.g.
    tol>0 paths whose while-loop makes counts dynamic)."""
    label: str
    trace: Callable[[], object]
    expected_dispatches: int | None = None


def synthetic_packed(j_nodes: int = SPMD_NODES, d_feat: int = 8,
                     dtype=np.float64, dy: int = 1):
    """Tiny circulant ring `PackedProblem` built from random arrays —
    shapes and slot layout are real, the numerics are irrelevant (entry
    points are traced, never executed). ``dy > 1`` builds the
    multi-output layout (`d` carries a trailing `[.., Dy]` axis)."""
    from repro.dist.dekrr_spmd import PackedProblem, _circulant_slot_table

    rng = np.random.default_rng(0)
    offsets = (1,)
    nbr_idx = _circulant_slot_table(offsets, j_nodes)
    k_slots = nbr_idx.shape[1]
    shp = dict(dtype=dtype)
    d_shape = (j_nodes, d_feat) if dy == 1 else (j_nodes, d_feat, dy)
    return PackedProblem(
        g=jnp.asarray(rng.standard_normal((j_nodes, d_feat, d_feat)),
                      **shp),
        d=jnp.asarray(rng.standard_normal(d_shape), **shp),
        s=jnp.asarray(rng.standard_normal((j_nodes, d_feat, d_feat)),
                      **shp),
        p=jnp.asarray(
            rng.standard_normal((j_nodes, k_slots, d_feat, d_feat)),
            **shp),
        theta_mask=jnp.ones((j_nodes, d_feat), dtype),
        nbr_idx=jnp.asarray(nbr_idx),
        nbr_mask=jnp.ones((j_nodes, k_slots), dtype),
        offsets=offsets,
        node_dims=(d_feat,) * j_nodes,
        num_edges_directed=j_nodes * k_slots,
    )


def _tiny_solver():
    """Smallest real `DeKRRSolver` (ring of 3, cos_bias) — needed only for
    the streaming-ingest trace, whose state layout `init_stream_aux`
    derives from a solver."""
    from repro.core.dekrr import DeKRRConfig, DeKRRSolver, NodeData
    from repro.core.graph import ring
    from repro.core.rff import FeatureMap

    j_nodes, dim_in, freqs, n_j = 3, 2, 4, 6
    rng = np.random.default_rng(0)
    fmaps = [FeatureMap(omega=jnp.asarray(rng.standard_normal((freqs,
                                                               dim_in))),
                        bias=jnp.asarray(rng.uniform(0, 2 * np.pi, freqs)),
                        kind="cos_bias")
             for _ in range(j_nodes)]
    data = [NodeData(x=jnp.asarray(rng.standard_normal((dim_in, n_j))),
                     y=jnp.asarray(rng.standard_normal(n_j)))
            for _ in range(j_nodes)]
    return DeKRRSolver(ring(j_nodes), fmaps, data, DeKRRConfig(),
                       build_aux=False)


def batched_entry_points() -> list[EntryPoint]:
    """Single-host entry points: `solve_batched`, `async_solve_batched`,
    `chebyshev_solve_packed` (every backend × {tol=0, tol>0} where
    applicable, at Dy=1 and the multi-output Dy=3 layout — the Dy axis
    folds into the kernel row dimension, so the dispatch pins are
    identical), the ops wrappers, streaming ingest."""
    from repro.core.acceleration import chebyshev_solve_packed
    from repro.dist.async_gossip import async_solve_batched
    from repro.dist.dekrr_spmd import _BACKENDS, solve_batched

    packed = synthetic_packed()
    packed_dy = synthetic_packed(dy=3)
    key = jax.random.PRNGKey(0)
    sync_expect = {"xla": 0, "pallas": ROUNDS, "pallas_fused": 1}
    async_expect = {"xla": 0, "pallas": ROUNDS, "pallas_fused": 1}
    cheb_expect = {"xla": 0, "pallas": ROUNDS, "pallas_fused": 1}
    eps = []
    for b in _BACKENDS:
        eps.append(EntryPoint(
            f"solve_batched[backend={b},tol=0]",
            lambda b=b: jax.make_jaxpr(
                lambda pk: solve_batched(pk, ROUNDS, backend=b))(packed),
            sync_expect[b]))
        eps.append(EntryPoint(
            f"solve_batched[backend={b},tol>0]",
            lambda b=b: jax.make_jaxpr(
                lambda pk: solve_batched(pk, ROUNDS, backend=b,
                                         tol=1e-3))(packed)))
        eps.append(EntryPoint(
            f"solve_batched[backend={b},tol=0,dy=3]",
            lambda b=b: jax.make_jaxpr(
                lambda pk: solve_batched(pk, ROUNDS,
                                         backend=b))(packed_dy),
            sync_expect[b]))
        # return_trace pins to the SAME dispatch count as the plain solve
        # — the convergence trace rides the existing scan/while carry and
        # must never add a kernel launch or a host callback.
        eps.append(EntryPoint(
            f"solve_batched[backend={b},tol=0,trace]",
            lambda b=b: jax.make_jaxpr(
                lambda pk: solve_batched(pk, ROUNDS, backend=b,
                                         return_trace=True))(packed),
            sync_expect[b]))
        eps.append(EntryPoint(
            f"solve_batched[backend={b},tol>0,trace]",
            lambda b=b: jax.make_jaxpr(
                lambda pk: solve_batched(pk, ROUNDS, backend=b, tol=1e-3,
                                         return_trace=True))(packed)))
        eps.append(EntryPoint(
            f"async_solve_batched[backend={b},tol=0]",
            lambda b=b: jax.make_jaxpr(
                lambda pk, k: async_solve_batched(pk, ROUNDS, k,
                                                  backend=b))(packed, key),
            async_expect[b]))
        eps.append(EntryPoint(
            f"async_solve_batched[backend={b},tol>0]",
            lambda b=b: jax.make_jaxpr(
                lambda pk, k: async_solve_batched(
                    pk, ROUNDS, k, backend=b, tol=1e-3))(packed, key)))
        eps.append(EntryPoint(
            f"async_solve_batched[backend={b},tol=0,dy=3]",
            lambda b=b: jax.make_jaxpr(
                lambda pk, k: async_solve_batched(
                    pk, ROUNDS, k, backend=b))(packed_dy, key),
            async_expect[b]))
        eps.append(EntryPoint(
            f"async_solve_batched[backend={b},tol=0,trace]",
            lambda b=b: jax.make_jaxpr(
                lambda pk, k: async_solve_batched(
                    pk, ROUNDS, k, backend=b,
                    return_trace=True))(packed, key),
            async_expect[b]))
        eps.append(EntryPoint(
            f"async_solve_batched[backend={b},tol=0,stats]",
            lambda b=b: jax.make_jaxpr(
                lambda pk, k: async_solve_batched(
                    pk, ROUNDS, k, backend=b,
                    return_stats=True))(packed, key),
            async_expect[b]))
        eps.append(EntryPoint(
            f"async_solve_batched[backend={b},tol>0,trace]",
            lambda b=b: jax.make_jaxpr(
                lambda pk, k: async_solve_batched(
                    pk, ROUNDS, k, backend=b, tol=1e-3,
                    return_trace=True))(packed, key)))
        eps.append(EntryPoint(
            f"chebyshev_solve_packed[backend={b}]",
            lambda b=b: jax.make_jaxpr(
                lambda pk: chebyshev_solve_packed(
                    pk, 0.9, 0.0, num_iters=ROUNDS, backend=b))(packed),
            cheb_expect[b]))
        eps.append(EntryPoint(
            f"chebyshev_solve_packed[backend={b},dy=3]",
            lambda b=b: jax.make_jaxpr(
                lambda pk: chebyshev_solve_packed(
                    pk, 0.9, 0.0, num_iters=ROUNDS,
                    backend=b))(packed_dy),
            cheb_expect[b]))
        eps.append(EntryPoint(
            f"chebyshev_solve_packed[backend={b},trace]",
            lambda b=b: jax.make_jaxpr(
                lambda pk: chebyshev_solve_packed(
                    pk, 0.9, 0.0, num_iters=ROUNDS, backend=b,
                    return_trace=True))(packed),
            cheb_expect[b]))
    eps.append(EntryPoint("ops.dekrr_step", _trace_ops_step, 1))
    eps.append(EntryPoint("ops.dekrr_solve", _trace_ops_solve, 1))
    eps.append(EntryPoint("ops.rff_features", _trace_ops_rff_features, 1))
    eps.append(EntryPoint("StreamingDeKRR.ingest", _trace_ingest, 0))
    # Serving answer wave (repro.serve.dekrr.answer_wave) on the tiny
    # 3-node cos_bias snapshot: xla paths emit no pallas_call; the pallas
    # paths dispatch one featurize kernel per node (J = 3) on both the
    # full-precision (rff_features) and bf16 (rff_features_lowp) routes.
    for backend, precision, pin in (("xla", None, 0), ("pallas", None, 3),
                                    ("xla", "bf16", 0),
                                    ("pallas", "bf16", 3)):
        label = (f"serve.answer_wave[backend={backend}"
                 + (f",precision={precision}" if precision else "") + "]")
        eps.append(EntryPoint(
            label,
            lambda backend=backend, precision=precision:
                _trace_serve_wave(backend, precision),
            pin))
    return eps


def _trace_ops_step():
    from repro.kernels import ops

    packed = synthetic_packed()
    self_idx = jnp.arange(packed.num_nodes, dtype=jnp.int32)
    return jax.make_jaxpr(
        lambda pk: ops.dekrr_step(pk.g, pk.d, pk.s, pk.p, pk.d * 0,
                                  pk.nbr_idx, self_idx, pk.nbr_mask)
    )(packed)


def _trace_ops_solve():
    from repro.kernels import ops

    packed = synthetic_packed()
    self_idx = jnp.arange(packed.num_nodes, dtype=jnp.int32)
    return jax.make_jaxpr(
        lambda pk: ops.dekrr_solve(pk.g, pk.d, pk.s, pk.p, pk.d * 0,
                                   pk.nbr_idx, self_idx, pk.nbr_mask,
                                   num_rounds=ROUNDS)
    )(packed)


def _trace_serve_wave(backend: str, precision: str | None):
    """Trace one serving answer wave: the staged snapshot's θ/bound
    constants are concrete (staged once per published version) and the
    query columns are the tracer — exactly the per-wave split
    `repro.serve.dekrr._serve_wave` dispatches."""
    from repro.serve.dekrr import answer_wave, stage_snapshot
    from repro.stream.runtime import ServeSnapshot, StalenessBound

    solver = _tiny_solver()
    rng = np.random.default_rng(3)
    fmaps = tuple(solver.feature_maps)
    theta = tuple(jnp.asarray(rng.standard_normal(fm.num_features))
                  for fm in fmaps)
    snap = ServeSnapshot(feature_maps=fmaps, theta=theta,
                         staleness=StalenessBound(0, 0, 0, 0.0))
    st = stage_snapshot(snap, backend=backend, precision=precision)
    dtype = st.dtype if precision is None else jnp.float32
    x = jnp.zeros((snap.input_dim, 8), dtype)
    return jax.make_jaxpr(lambda xx: answer_wave(st, xx))(x)


def _trace_ops_rff_features():
    from repro.kernels import ops

    fm = _tiny_solver().feature_maps[0]
    x = jnp.zeros((fm.omega.shape[1], 8), fm.omega.dtype)
    return jax.make_jaxpr(
        lambda om, b, xx: ops.rff_features(
            om, b, xx, scale=float(np.sqrt(2.0 / fm.num_frequencies)))
    )(fm.omega, fm.bias, x)


def _trace_ingest():
    """Trace the streaming minibatch fold (`StreamingDeKRR.ingest` →
    `repro.stream.updates.ingest`) with the array state as tracers and
    the host-side staging (tables, minibatch padding) concrete — exactly
    the split the runtime uses."""
    import dataclasses as dc

    from repro.stream.updates import ingest, init_stream_aux

    aux = init_stream_aux(_tiny_solver())
    rng = np.random.default_rng(1)
    xb = rng.standard_normal((2, 3))
    yb = rng.standard_normal(3)
    return jax.make_jaxpr(
        lambda binv, zy, st, pt: ingest(
            dc.replace(aux, binv=binv, zy=zy, st=st, pt=pt), 0, xb, yb
        ).binv
    )(aux.binv, aux.zy, aux.st, aux.pt)


def spmd_entry_points() -> list[EntryPoint]:
    """SPMD entry points — need `SPMD_NODES` devices (forced host devices
    on CPU). Dispatch pins follow the make_spmd_solver docstring: rounds
    never fuse across the per-round exchange, so the Pallas backends run
    one per-round kernel dispatch per round."""
    from jax.sharding import Mesh

    from repro.dist.async_gossip import make_async_spmd_solver
    from repro.dist.dekrr_spmd import make_spmd_solver

    if len(jax.devices()) < SPMD_NODES:
        raise RuntimeError(
            f"SPMD lint needs >= {SPMD_NODES} devices (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={SPMD_NODES})")
    mesh = Mesh(np.array(jax.devices()[:SPMD_NODES]), ("nodes",))
    packed = synthetic_packed(j_nodes=SPMD_NODES)
    key = jax.random.PRNGKey(0)
    sync_expect = {"xla": 0, "pallas": ROUNDS}
    eps = []
    for mode in ("ppermute", "allgather"):
        for backend in ("xla", "pallas"):
            for tol, pin in ((0.0, sync_expect[backend]), (1e-3, None)):
                run = make_spmd_solver(mesh, "nodes", mode=mode,
                                       backend=backend)
                eps.append(EntryPoint(
                    f"make_spmd_solver[mode={mode},backend={backend},"
                    f"tol{'>0' if tol else '=0'}]",
                    lambda run=run, tol=tol: jax.make_jaxpr(
                        lambda pk: run(pk, ROUNDS, tol=tol))(packed),
                    pin))
                arun = make_async_spmd_solver(mesh, "nodes", mode=mode,
                                              backend=backend)
                eps.append(EntryPoint(
                    f"make_async_spmd_solver[mode={mode},"
                    f"backend={backend},tol{'>0' if tol else '=0'}]",
                    lambda arun=arun, tol=tol: jax.make_jaxpr(
                        lambda pk, k: arun(pk, ROUNDS, k,
                                           tol=tol))(packed, key),
                    pin))
                # Trace variants pin the SAME counts — the per-device
                # residual/broadcast series rides the existing scan ys /
                # while carry; wire accounting reduces outside shard_map.
                eps.append(EntryPoint(
                    f"make_spmd_solver[mode={mode},backend={backend},"
                    f"tol{'>0' if tol else '=0'},trace]",
                    lambda run=run, tol=tol: jax.make_jaxpr(
                        lambda pk: run(pk, ROUNDS, tol=tol,
                                       return_trace=True))(packed),
                    pin))
                eps.append(EntryPoint(
                    f"make_async_spmd_solver[mode={mode},"
                    f"backend={backend},tol{'>0' if tol else '=0'},trace]",
                    lambda arun=arun, tol=tol: jax.make_jaxpr(
                        lambda pk, k: arun(pk, ROUNDS, k, tol=tol,
                                           return_trace=True))(packed, key),
                    pin))
    return eps


# --------------------------------------------------------------------------
# Driver
# --------------------------------------------------------------------------
def lint_program(closed, where: str, *,
                 expected_dispatches: int | None = None) -> list[Finding]:
    """Run every structural rule on one traced program."""
    findings = []
    findings += check_no_callbacks_in_loops(closed, where)
    findings += check_dispatch_contract(closed, expected_dispatches, where)
    findings += check_ppermute_bijections(closed, where)
    findings += check_loop_downcasts(closed, where)
    findings += check_replication(closed, where)
    findings += check_traced_vmem(closed, where)
    return findings


def run_pass(*, spmd: bool | None = None,
             entry_points: Iterable[EntryPoint] | None = None
             ) -> list[Finding]:
    """Trace and lint every solver entry point. ``spmd=None`` includes the
    SPMD programs iff enough devices are visible; a trace that itself
    crashes is reported as a J000 finding rather than aborting the pass."""
    if entry_points is None:
        entry_points = list(batched_entry_points())
        if spmd is None:
            spmd = len(jax.devices()) >= SPMD_NODES
        if spmd:
            entry_points = entry_points + spmd_entry_points()
    findings = []
    for ep in entry_points:
        try:
            closed = ep.trace()
        except Exception as exc:  # pragma: no cover - trace regression
            findings.append(Finding(
                "jaxpr", "J000", ep.label,
                f"entry point failed to trace: {type(exc).__name__}: "
                f"{exc}"))
            continue
        findings += lint_program(
            closed, ep.label, expected_dispatches=ep.expected_dispatches)
    return findings
