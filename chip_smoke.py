"""Run DeKRR-DDRF end to end on a TPU and check it against a float64 host
reference.

    python chip_smoke.py              # one chip: the paper's Table 2 cell
    python chip_smoke.py --chips 4    # the SPMD solvers on a 4-chip host

One chip. The paper's Table 2 deployment (twitter shape): J = 10 nodes on
circulant(10, (1, 2)), non-IID-by-|y| partition of all N = 98,704 samples
at d = 77, D̄ = 130 energy-selected DDRF features per node, λ, σ and c as
in `benchmarks/common.py`. It runs the normal entry points in float32
with x64 off, the TPU's precision:

    select_features → pack_problem (Pallas Gram kernel)
      → solve_batched(backend="pallas_fused", tol) and, for comparison,
        solve_batched(backend="xla", tol)
      → StreamingDeKRR → SnapshotRegistry.publish_from
      → DeKRRServeEngine (Pallas featurize kernel) answers 500 test-split
        queries,

and compares θ and the answers with `DeKRRSolver.solve_exact` (the
ragged reference's exact limit point) computed in float64 on the host CPU
from the same float32 inputs.

Four chips (``--chips 4``): J = 4 nodes on circulant(4, (1,)), one node
per chip, through `make_spmd_solver` and `make_async_spmd_solver` in both
exchange modes ("ppermute", "allgather") and both per-device backends
("xla", "pallas"); each result is compared with the single-chip batched
solver of the same process on the same backend. Nothing else runs.

Exits non-zero when no TPU is found, when a phase raises, or when a
comparison is off. Timings printed are smoke timings of single calls,
not benchmark numbers. The last stdout line is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DATASET = "twitter"           # Tab. 2 shape: d = 77, N = 98,704
D_BAR = 130                   # Tab. 2 D̄ for twitter
CANDIDATE_RATIO = 20          # DDRF D0/D (paper, after [33])
SIGMA, LAM = 1.0, 1e-6        # benchmarks/common.py
C_NEI = 0.01                  # middle of benchmarks/common.py's C_GRID, × N
ROUND_BUDGET = 4000
# Early stop: max|Δθ| over a 32-round chunk. θ is ~1e-3 in this cell, so
# 1e-7 is ~1e-4 of it — two orders above the f32 rounding floor of one
# round (~1e-9 here), so the stop is reachable in f32.
TOL = 1e-7
CHUNK = 32                    # solve_batched's default chunk for pallas_fused
QUERIES_PER_NODE = 50
F32_U = 2.0 ** -24            # f32 unit roundoff


def fail(what: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {what}")


def require(ok: bool, what: str) -> None:
    if not ok:
        fail(what)


def timed(label: str, fn, *, repeat: bool = True):
    """Run `fn` and print smoke timings: the first call includes tracing
    and compilation; the second (when `repeat`) is the compiled run."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    line = f"[smoke timing] {label}: first call {t1 - t0:.3f} s"
    if repeat:
        out = jax.block_until_ready(fn())
        line += f", second call {time.perf_counter() - t1:.3f} s"
    print(line, flush=True)
    return out


def require_tpu(chips: int):
    """The chip, or exit non-zero before anything is dispatched."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
              f"found {len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    if jax.config.jax_enable_x64:
        fail("x64 is on; the chip path runs float32")
    from repro.kernels import ops

    # Every kernel wrapper on the path leaves `interpret` at its default,
    # which is interpret mode off-TPU: the platform check above is what
    # makes each of them compile for the chip.
    require(not ops._interpret_default(), "Pallas kernels would interpret")
    return devices


def make_problem(j_nodes: int, topology, seed: int):
    """float32 node shards (train/test) and per-node DDRF feature maps."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import NodeData, select_features
    from repro.data.synthetic import (make_dataset, partition,
                                      train_test_split_nodes)

    ds = make_dataset(DATASET, seed=seed)
    train, test = train_test_split_nodes(
        partition(ds, j_nodes, mode="noniid_y", seed=seed), seed=seed)
    f32 = lambda a: jnp.asarray(np.asarray(a), jnp.float32)
    train = [NodeData(x=f32(t.x), y=f32(t.y)) for t in train]
    test = [NodeData(x=f32(t.x), y=f32(t.y)) for t in test]
    keys = jax.random.split(jax.random.PRNGKey(seed), j_nodes)

    def ddrf():
        return [select_features(keys[j], ds.dim, D_BAR, SIGMA, train[j].x,
                                train[j].y, method="energy",
                                candidate_ratio=CANDIDATE_RATIO)
                for j in range(j_nodes)]

    fmaps = timed(f"DDRF select_features (J={j_nodes}, D0={CANDIDATE_RATIO}"
                  f"x{D_BAR})", ddrf)
    return train, test, fmaps


def host_reference(topology, fmaps, train, c_nei: float):
    """The ragged reference solver in float64 on the host CPU, built from
    the same float32 inputs: (solver, exact θ per node, ρ(M))."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DeKRRConfig, DeKRRSolver, FeatureMap, NodeData

    f64 = lambda a: jnp.asarray(np.asarray(a), jnp.float64)
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        fm64 = [FeatureMap(omega=f64(f.omega), bias=f64(f.bias), kind=f.kind)
                for f in fmaps]
        tr64 = [NodeData(x=f64(t.x), y=f64(t.y)) for t in train]
        ref = DeKRRSolver(topology, fm64, tr64,
                          DeKRRConfig(lam=LAM, c_nei=c_nei))
        theta = [np.asarray(t) for t in ref.solve_exact().theta]
        rho = ref.spectral_radius()
    return ref, theta, rho


def theta_tolerance(rho: float, n_node: int, scale: float) -> float:
    """Relative tolerance for an f32 θ against the f64 exact limit point.

    Each Eq. 17 block is an f32 sum over a node's N_j samples: its
    rounding grows like √N_j·u. The fixed point θ* = (I − M)⁻¹b passes
    relative perturbations of M and b through with gain up to 1/(1 − ρ).
    Stopping when a 32-round chunk moves θ by less than TOL leaves
    ~ρ³²/(1 − ρ³²)·TOL of iteration error on top."""
    chunk_gain = rho ** CHUNK / (1.0 - rho ** CHUNK)
    return (F32_U * math.sqrt(n_node) / (1.0 - rho)
            + chunk_gain * TOL / scale)


def one_chip(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import DeKRRConfig, DeKRRSolver, circulant
    from repro.dist import pack_problem, solve_batched
    from repro.serve.dekrr import DeKRRServeEngine, KernelQuery
    from repro.stream import SnapshotRegistry, StreamConfig, StreamingDeKRR

    j_nodes = 10
    topology = circulant(j_nodes, (1, 2))
    train, test, fmaps = make_problem(j_nodes, topology, seed)
    n = sum(t.num_samples for t in train)
    n_node = max(t.num_samples for t in train)
    print(f"deployment: J={j_nodes} circulant(1,2), N={n} train samples "
          f"(N_j <= {n_node}), d={train[0].x.shape[0]}, D_j={D_BAR}, "
          f"lambda={LAM}, sigma={SIGMA}, c_nei={C_NEI}*N", flush=True)
    solver = DeKRRSolver(topology, fmaps, train,
                         DeKRRConfig(lam=LAM, c_nei=C_NEI * n),
                         build_aux=False)

    packed = timed("pack_problem (Pallas Gram)",
                   lambda: pack_problem(solver))
    require(packed.g.dtype == jnp.float32, f"packed dtype {packed.g.dtype}")

    def solve(backend):
        return lambda: solve_batched(packed, ROUND_BUDGET, backend=backend,
                                     tol=TOL, chunk_rounds=CHUNK,
                                     return_rounds=True)

    lowered = jax.jit(lambda pk: solve_batched(
        pk, ROUND_BUDGET, backend="pallas_fused", tol=TOL,
        chunk_rounds=CHUNK)).lower(packed).as_text()
    require("tpu_custom_call" in lowered,
            "the fused solve lowered without a TPU kernel")
    th_fused, r_fused = timed("solve_batched pallas_fused", solve(
        "pallas_fused"))
    th_xla, r_xla = timed("solve_batched xla", solve("xla"))

    registry = SnapshotRegistry()

    def stream_and_publish():
        stream = StreamingDeKRR(
            solver, StreamConfig(backend="pallas_fused", tol=TOL,
                                 rounds_per_epoch=ROUND_BUDGET))
        report = stream.solve()
        registry.publish_from(stream)
        return stream, report

    stream, report = timed("StreamingDeKRR (packs) + solve + publish",
                           stream_and_publish)

    xq = np.concatenate([np.asarray(t.x)[:, :QUERIES_PER_NODE]
                         for t in test], axis=1)
    engine = DeKRRServeEngine(registry)
    require(engine.backend == "pallas", f"serve backend {engine.backend}")

    def serve():
        queries = [KernelQuery(uid=i, x=xq[:, i]) for i in range(xq.shape[1])]
        return np.array([q.prediction for q in engine.run(queries)])

    answers = timed(f"DeKRRServeEngine {xq.shape[1]} queries", serve)

    t0 = time.perf_counter()
    ref, theta_ref, rho = host_reference(topology, fmaps, train, C_NEI * n)
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        answers_ref = np.asarray(ref.predict(
            [jnp.asarray(t) for t in theta_ref],
            jnp.asarray(xq, jnp.float64)))
    print(f"[smoke timing] host float64 reference: "
          f"{time.perf_counter() - t0:.3f} s", flush=True)

    theta_ref = np.stack(theta_ref)                     # all D_j = D̄
    scale = float(np.max(np.abs(theta_ref)))
    tol_theta = theta_tolerance(rho, n_node, scale)
    print(f"reference: rho(M)={rho:.6f}, max|theta|={scale:.6e}, "
          f"theta tolerance (relative) {tol_theta:.3e}", flush=True)

    for name, theta, rounds in (
            ("pallas_fused", th_fused, r_fused), ("xla", th_xla, r_xla),
            ("stream", stream.theta, report.rounds_run)):
        theta = np.asarray(theta, np.float64)
        require(theta.shape == theta_ref.shape and np.isfinite(theta).all(),
                f"{name} theta shape {theta.shape} / finite")
        rounds = int(rounds)
        err = float(np.max(np.abs(theta - theta_ref))) / scale
        print(f"theta[{name}]: rounds to tol {rounds} of {ROUND_BUDGET}, "
              f"max|theta - ref| / max|ref| = {err:.3e}", flush=True)
        require(rounds < ROUND_BUDGET, f"{name} did not reach tol {TOL}")
        require(err <= tol_theta, f"{name} theta error {err:.3e} > "
                f"{tol_theta:.3e}")
    gap = float(np.max(np.abs(np.asarray(th_fused) - np.asarray(th_xla))))
    print(f"max|theta[pallas_fused] - theta[xla]| / max|ref| = "
          f"{gap / scale:.3e}", flush=True)

    # Answer tolerance: f = mean_j θ_jᵀ z_j(x) with |z| <= √(2/D̄). The θ
    # error moves it by at most √(2D̄)·tol_theta·scale; f32 featurize moves
    # each z by at most √(2/D̄)·(γ_d·max|ωᵀx + b| + 2u) (dot of d terms,
    # then cos), weighted by ‖θ_j‖₁; the f32 GEMV adds γ_D̄·√(2/D̄)·‖θ_j‖₁.
    fm = [(np.asarray(f.omega, np.float64), np.asarray(f.bias, np.float64))
          for f in fmaps]
    arg = max(float(np.max(np.abs(om) @ np.abs(xq) + np.abs(b)[:, None]))
              for om, b in fm)
    d_in = xq.shape[0]
    gamma = lambda k: k * F32_U / (1 - k * F32_U)
    l1 = float(np.max(np.sum(np.abs(theta_ref), axis=1)))
    zmax = math.sqrt(2.0 / D_BAR)
    tol_answer = (math.sqrt(2.0 * D_BAR) * tol_theta * scale
                  + zmax * l1 * (gamma(d_in) * arg + 2 * F32_U
                                 + gamma(D_BAR)))
    require(answers.shape == answers_ref.shape
            and np.isfinite(answers).all(), "served answers shape / finite")
    aerr = float(np.max(np.abs(answers - answers_ref)))
    print(f"served answers: {answers.size} queries, max|answer|="
          f"{float(np.max(np.abs(answers_ref))):.6e}, max|answer - ref| = "
          f"{aerr:.3e} (tolerance {tol_answer:.3e})", flush=True)
    require(aerr <= tol_answer, f"answer error {aerr:.3e} > {tol_answer:.3e}")


def four_chips(seed: int, devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import AsyncGossipConfig, DeKRRConfig, DeKRRSolver
    from repro.core import circulant
    from repro.dist import (async_solve_batched, make_async_spmd_solver,
                            make_spmd_solver, pack_problem, solve_batched)

    j_nodes, rounds = 4, 256
    topology = circulant(j_nodes, (1,))
    train, _, fmaps = make_problem(j_nodes, topology, seed)
    n = sum(t.num_samples for t in train)
    solver = DeKRRSolver(topology, fmaps, train,
                         DeKRRConfig(lam=LAM, c_nei=C_NEI * n),
                         build_aux=False)
    packed = timed("pack_problem (Pallas Gram)",
                   lambda: pack_problem(solver))
    _, _, rho = host_reference(topology, fmaps, train, C_NEI * n)
    # The SPMD program runs each node's round with the same f32 operations
    # as the batched one, in another order: one round differs by at most
    # γ_D̄ relative, and the contraction sums that over the rounds with
    # gain 1/(1 − ρ).
    tol = D_BAR * F32_U / (1.0 - rho)
    print(f"deployment: J={j_nodes} circulant(1), N={n} train samples, "
          f"D_j={D_BAR}, {rounds} rounds; rho(M)={rho:.6f}, relative "
          f"tolerance {tol:.3e}", flush=True)

    mesh = Mesh(np.array(devices[:j_nodes]), ("nodes",))
    key = jax.random.PRNGKey(seed)
    gossip = AsyncGossipConfig(prob=0.5)
    for backend in ("xla", "pallas"):
        want = timed(f"solve_batched {backend} (one chip)",
                     lambda: solve_batched(packed, rounds, backend=backend))
        want_async = timed(
            f"async_solve_batched {backend} (one chip)",
            lambda: async_solve_batched(packed, rounds, key, config=gossip,
                                        backend=backend))
        for mode in ("ppermute", "allgather"):
            sync = make_spmd_solver(mesh, "nodes", mode, backend=backend)
            got = timed(f"make_spmd_solver {mode}/{backend}",
                        lambda: sync(packed, rounds))
            asyn = make_async_spmd_solver(mesh, "nodes", mode,
                                          backend=backend)
            got_async = timed(f"make_async_spmd_solver {mode}/{backend}",
                              lambda: asyn(packed, rounds, key, gossip))
            for name, out, ref in (("sync", got, want),
                                   ("async", got_async, want_async)):
                shards = out.addressable_shards
                placed = sorted((s.device.id, s.index[0].start)
                                for s in shards)
                require(len({d for d, _ in placed}) == j_nodes
                        and [i for _, i in placed] == list(range(j_nodes))
                        and all(s.data.shape[0] == 1 for s in shards),
                        f"{name} {mode}/{backend}: nodes not one per chip "
                        f"({placed})")
                got_np, ref_np = np.asarray(out), np.asarray(ref)
                scale = float(np.max(np.abs(ref_np)))
                err = float(np.max(np.abs(got_np - ref_np))) / scale
                print(f"{name} {mode}/{backend}: (device, node) {placed}, "
                      f"max|spmd - batched| / max|batched| = {err:.3e}",
                      flush=True)
                require(bool(np.isfinite(got_np).all()) and err <= tol,
                        f"{name} {mode}/{backend} error {err:.3e} > "
                        f"{tol:.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = require_tpu(args.chips)
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    print(f"device: {devices[0].device_kind} ({devices[0].platform}), "
          f"{len(devices)} visible, using {args.chips}; jax "
          f"{jax.__version__}; compile cache {cache}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args.seed)
    else:
        four_chips(args.seed, devices)
    print(f"[smoke timing] total {time.perf_counter() - t0:.3f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
