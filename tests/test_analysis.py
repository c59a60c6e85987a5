"""Static-analysis suite: lints the live codebase (a violation anywhere in
src/tests/benchmarks fails tier-1), pins the dispatch-count contract per
backend, property-tests the VMEM estimator against the kernel docstring
formulas, and seeds one violation of every lint class to prove the passes
actually detect what they claim to.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from conftest import REPO_ROOT, subprocess_env

from repro.analysis import (VMEM_BUDGET_BYTES, VmemBudgetError,
                            check_index_table, estimate_dekrr_async_solve,
                            estimate_dekrr_cheb_solve, estimate_dekrr_solve,
                            estimate_dekrr_step, estimate_flash_decode,
                            estimate_rff_gram, render_json, render_report)
from repro.analysis import conventions
from repro.analysis import jaxpr_lint as JL
from repro.analysis.report import Finding
from repro.kernels import ops


# ---------------------------------------------------------------------------
# VMEM estimator: docstring anchors, monotonicity, budget gate
# ---------------------------------------------------------------------------
def test_vmem_docstring_anchors():
    # dekrr_solve: "J ≤ 256, D ≤ 512, K = 4 at f32 that is ~13.7 MB"
    est = estimate_dekrr_solve(t_rows=256, d_feat=512, k_slots=4)
    assert est.bytes == 13637632 and est.fits
    # dekrr_step at the same point holds one θ table + single buffers
    st = estimate_dekrr_step(t_rows=16, d_feat=512, k_slots=4)
    assert st.bytes == 6330368 and st.fits
    # rff_gram: "D ≤ 512, d ≤ 160, Bn = 1024 that is < 5 MB"
    rg = estimate_rff_gram(d_feat=512, d_in=160, block_n=1024)
    assert rg.bytes == 4132864 and rg.bytes < 5 * 2**20
    # flash_decode: "G ≤ 8, dh = 128, block_s = 512: < 1 MB"
    fd = estimate_flash_decode(g_heads=8, head_dim=128, block_s=512)
    assert fd.bytes == 544864 and fd.bytes < 2**20
    # dekrr_async_solve: two θ tables + sent + working/init buffer tables
    av = estimate_dekrr_async_solve(t_rows=128, b_rows=512, d_feat=512,
                                    k_slots=4)
    assert av.bytes == 15996928 and av.fits
    # dekrr_cheb_solve: two θ tables + direction table
    cv = estimate_dekrr_cheb_solve(t_rows=256, j_rows=256, d_feat=512,
                                   k_slots=4)
    assert cv.bytes == 15210496 and cv.fits


def test_vmem_monotone_in_shape():
    def solve_bytes(d, k):
        return estimate_dekrr_solve(t_rows=64, d_feat=d, k_slots=k).bytes

    prev = 0
    for d in (128, 256, 384, 512, 1024):
        cur = solve_bytes(d, 4)
        assert cur > prev
        prev = cur
    prev = 0
    for k in (1, 2, 4, 8):
        cur = solve_bytes(256, k)
        assert cur > prev
        prev = cur


def test_vmem_f64_itemsize_capped():
    # x64 callers run interpret-mode or downcast — budgeting 8 B/elem
    # would spuriously reject deployable shapes.
    a = estimate_dekrr_step(t_rows=64, d_feat=512, k_slots=4, itemsize=8)
    b = estimate_dekrr_step(t_rows=64, d_feat=512, k_slots=4, itemsize=4)
    assert a.bytes == b.bytes


def test_vmem_over_budget_raises_with_formula():
    est = estimate_dekrr_solve(t_rows=1024, d_feat=1024, k_slots=8)
    assert not est.fits
    with pytest.raises(VmemBudgetError) as exc:
        est.check()
    msg = str(exc.value)
    assert "2*T*D + 2*(2+K)*D^2 + 3*D" in msg
    assert str(VMEM_BUDGET_BYTES) in msg


def test_ops_dekrr_solve_rejects_over_budget_before_dispatch():
    # eval_shape runs the wrapper body with tracers only — nothing is
    # allocated and no pallas_call is built, so a raise here IS "before
    # dispatch".
    f32 = jnp.float32
    d_feat, j, k = 1024, 2, 8
    spec = jax.ShapeDtypeStruct
    args = (spec((j, d_feat, d_feat), f32), spec((j, d_feat), f32),
            spec((j, d_feat, d_feat), f32),
            spec((j, k, d_feat, d_feat), f32), spec((j, d_feat), f32),
            spec((j, k), jnp.int32), spec((j,), jnp.int32),
            spec((j, k), f32))
    with pytest.raises(VmemBudgetError, match=r"2\*T\*D"):
        jax.eval_shape(lambda *a: ops.dekrr_solve(*a, num_rounds=3), *args)


def test_ops_rff_gram_rejects_over_budget_concrete():
    d_feat, d_in, n = 2048, 160, 256
    omega = jnp.zeros((d_feat, d_in))
    with pytest.raises(VmemBudgetError, match=r"D\*d \+ d\*Bn"):
        ops.rff_gram(omega, jnp.zeros(d_feat), jnp.zeros((d_in, n)),
                     jnp.zeros(n), scale=1.0)


# ---------------------------------------------------------------------------
# Scalar-prefetch index-table bounds checks
# ---------------------------------------------------------------------------
def _tiny_dekrr_operands(j=2, d_feat=4, k=1):
    g = jnp.tile(jnp.eye(d_feat), (j, 1, 1))
    d = jnp.ones((j, d_feat))
    s = jnp.zeros((j, d_feat, d_feat))
    p = jnp.zeros((j, k, d_feat, d_feat))
    theta = jnp.zeros((j, d_feat))
    nbr_idx = jnp.zeros((j, k), jnp.int32)
    self_idx = jnp.arange(j, dtype=jnp.int32)
    nbr_mask = jnp.ones((j, k))
    return g, d, s, p, theta, nbr_idx, self_idx, nbr_mask


def test_check_index_table():
    check_index_table("t", np.array([0, 3, 1]), 4)
    with pytest.raises(ValueError, match="scalar-prefetched"):
        check_index_table("t", np.array([0, 4]), 4)
    with pytest.raises(ValueError, match="integer"):
        check_index_table("t", np.array([0.5]), 4)


def test_ops_rejects_out_of_range_slot_index():
    g, d, s, p, theta, nbr_idx, self_idx, nbr_mask = _tiny_dekrr_operands()
    bad = nbr_idx.at[0, 0].set(7)           # θ table has 2 rows
    with pytest.raises(ValueError, match="scalar-prefetched"):
        ops.dekrr_step(g, d, s, p, theta, bad, self_idx, nbr_mask)
    with pytest.raises(ValueError, match="scalar-prefetched"):
        ops.dekrr_solve(g, d, s, p, theta, bad, self_idx, nbr_mask,
                        num_rounds=2)
    # masked slots may carry any in-range-irrelevant garbage? No — but an
    # out-of-range index under a ZERO mask is never gathered with effect,
    # and the staging layer pads with the self index; the ops wrapper
    # therefore only validates LIVE slots:
    masked = nbr_mask.at[0, 0].set(0.0)
    out = ops.dekrr_step(g, d, s, p, theta, bad, self_idx, masked)
    assert out.shape == d.shape
    # self_idx is unmasked — always validated
    with pytest.raises(ValueError, match="self_idx"):
        ops.dekrr_step(g, d, s, p, theta, nbr_idx,
                       jnp.array([0, 9], jnp.int32), nbr_mask)


def test_pack_staging_rejects_out_of_range_slot_index():
    from repro.dist.dekrr_spmd import _validate_slot_table

    idx = np.array([[1], [0]], np.int32)
    mask = np.ones((2, 1))
    assert _validate_slot_table(idx, mask, 2) == 2
    with pytest.raises(ValueError, match="scalar-prefetched"):
        _validate_slot_table(np.array([[2], [0]], np.int32), mask, 2)
    with pytest.raises(ValueError, match="shape mismatch"):
        _validate_slot_table(idx, np.ones((2, 3)), 2)


def test_async_mask_table_guard():
    from repro.dist.async_gossip import _check_mask_table, init_async_state
    from repro.dist import async_gossip as AG

    _check_mask_table("t", np.ones((5, 3), bool), 5, 3)
    with pytest.raises(ValueError, match="activation-mask"):
        _check_mask_table("t", np.ones((5, 4), bool), 5, 3)
    # async_step_batched rejects a mis-sized per-round mask row
    packed = JL.synthetic_packed(j_nodes=4, d_feat=8)
    state = init_async_state(packed)
    with pytest.raises(ValueError, match="activation-mask"):
        AG.async_step_batched(packed, state, jnp.ones(5, bool))


# ---------------------------------------------------------------------------
# comm_bytes_per_round: static edge count, no device read-back
# ---------------------------------------------------------------------------
class _PoisonArray:
    """Fails the test if anything tries to materialize it on the host."""
    def __array__(self, *a, **k):
        raise AssertionError("comm_bytes_per_round read nbr_mask off "
                             "the device")


def test_comm_bytes_static_edge_count():
    from repro.dist.dekrr_spmd import comm_bytes_per_round

    packed = JL.synthetic_packed(j_nodes=4, d_feat=8)
    assert packed.num_edges_directed == int(
        np.count_nonzero(np.asarray(packed.nbr_mask)))
    want = comm_bytes_per_round(packed, "ppermute")
    # with the static count recorded, the mask array is never touched
    poisoned = dataclasses.replace(packed, nbr_mask=_PoisonArray())
    assert comm_bytes_per_round(poisoned, "ppermute") == want
    # NumPy fallback for hand-built problems matches
    legacy = dataclasses.replace(packed, num_edges_directed=None)
    assert comm_bytes_per_round(legacy, "ppermute") == want


def test_packed_static_fields_survive_jit():
    packed = JL.synthetic_packed(j_nodes=4, d_feat=8)
    out = jax.jit(lambda p: p)(packed)
    assert out.num_edges_directed == packed.num_edges_directed
    assert out.offsets == packed.offsets


# ---------------------------------------------------------------------------
# jaxpr lint: live entry points clean + dispatch-count pins
# ---------------------------------------------------------------------------
def _entry_point_map():
    return {ep.label: ep for ep in JL.batched_entry_points()}


def test_live_jaxpr_lint_clean():
    findings = JL.run_pass(spmd=False)
    assert findings == [], render_report(findings)


@pytest.mark.parametrize("backend,sync_n,async_n,cheb_n", [
    ("xla", 0, 0, 0), ("pallas", 5, 5, 5), ("pallas_fused", 1, 1, 1)])
def test_dispatch_count_contract(backend, sync_n, async_n, cheb_n):
    eps = _entry_point_map()
    for label, want in (
            (f"solve_batched[backend={backend},tol=0]", sync_n),
            (f"async_solve_batched[backend={backend},tol=0]", async_n),
            (f"chebyshev_solve_packed[backend={backend}]", cheb_n)):
        ep = eps[label]
        assert ep.expected_dispatches == want
        count, exact = JL.count_pallas_dispatches(ep.trace())
        assert exact and count == want


def test_ops_wrappers_dispatch_once():
    eps = _entry_point_map()
    for label in ("ops.dekrr_step", "ops.dekrr_solve"):
        count, exact = JL.count_pallas_dispatches(eps[label].trace())
        assert exact and count == 1
    count, exact = JL.count_pallas_dispatches(
        eps["StreamingDeKRR.ingest"].trace())
    assert exact and count == 0


# ---------------------------------------------------------------------------
# jaxpr lint: seeded violations (one per rule)
# ---------------------------------------------------------------------------
def _rules(findings):
    return [f.rule for f in findings]


def test_seeded_callback_in_loop_detected():
    def bad(x):
        def body(c, _):
            v = jax.pure_callback(
                lambda a: np.asarray(a),
                jax.ShapeDtypeStruct((), x.dtype), c)
            return c + v, None
        return lax.scan(body, x, None, length=3)[0]

    cj = jax.make_jaxpr(bad)(jnp.float64(1.0))
    assert "J001" in _rules(JL.lint_program(cj, "seed"))


def test_seeded_loop_downcast_detected():
    def bad(x):
        def body(c):
            return (c[0].astype(jnp.float32).astype(jnp.float64),
                    c[1] + 1)
        return lax.while_loop(lambda c: c[1] < 3, body, (x, 0))

    cj = jax.make_jaxpr(bad)(jnp.float64(1.0))
    assert "J004" in _rules(JL.lint_program(cj, "seed"))


def test_ppermute_bijection_helper():
    # identity-free ring shift is a bijection
    assert JL.ppermute_perm_errors([(i, (i + 1) % 4)
                                    for i in range(4)], 4) == []
    # duplicated destination
    assert JL.ppermute_perm_errors([(0, 1), (1, 1)], 4)
    # partial coverage over the axis
    assert JL.ppermute_perm_errors([(0, 1), (1, 0)], 4)
    # out-of-range endpoint
    assert JL.ppermute_perm_errors([(0, 4)], 4)


def test_seeded_dispatch_contract_violation_detected():
    eps = _entry_point_map()
    ep = eps["solve_batched[backend=pallas_fused,tol=0]"]
    findings = JL.lint_program(ep.trace(), ep.label,
                               expected_dispatches=3)   # truth is 1
    assert "J002" in _rules(findings)


SPMD_ANALYSIS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec
    from repro.analysis import jaxpr_lint as JL
    from jax import shard_map

    # live repo: all entry points (incl. SPMD ppermute/allgather) clean
    findings = JL.run_pass()
    assert not findings, [f.render() for f in findings]

    mesh = Mesh(np.array(jax.devices()[:4]), ("nodes",))
    P = PartitionSpec

    # seeded J003: non-bijective ppermute under shard_map
    def bad_perm(x):
        def prog(x):
            return lax.ppermute(x, "nodes", [(0, 1), (1, 2)])
        return shard_map(prog, mesh=mesh, in_specs=P("nodes"),
                         out_specs=P("nodes"), check_vma=False)(x)
    cj = jax.make_jaxpr(bad_perm)(jnp.zeros((4, 2)))
    rules = [f.rule for f in JL.lint_program(cj, "seed")]
    assert "J003" in rules, rules

    # seeded J005: device-varying while predicate gating a collective
    ring = [(i, (i + 1) % 4) for i in range(4)]
    def unreplicated_loop(x):
        def prog(x):
            me = lax.axis_index("nodes")
            def cond(c):
                return c[1] < me + 1
            def body(c):
                return (c[0] + lax.ppermute(c[0], "nodes", ring),
                        c[1] + 1)
            return lax.while_loop(cond, body, (x, 0))[0]
        return shard_map(prog, mesh=mesh, in_specs=P("nodes"),
                         out_specs=P("nodes"), check_vma=False)(x)
    cj = jax.make_jaxpr(unreplicated_loop)(jnp.zeros((4, 2)))
    rules = [f.rule for f in JL.lint_program(cj, "seed")]
    assert "J005" in rules, rules

    # negative: pmax-derived (replicated) predicate must stay clean
    def replicated_loop(x):
        def prog(x):
            def cond(c):
                return c[1] < 3
            def body(c):
                d = lax.pmax(jnp.max(c[0]), "nodes")
                return (c[0] + lax.ppermute(c[0], "nodes", ring)
                        + d * 0, c[1] + 1)
            return lax.while_loop(cond, body, (x, 0))[0]
        return shard_map(prog, mesh=mesh, in_specs=P("nodes"),
                         out_specs=P("nodes"), check_vma=False)(x)
    cj = jax.make_jaxpr(replicated_loop)(jnp.zeros((4, 2)))
    rules = [f.rule for f in JL.lint_program(cj, "seed")]
    assert "J005" not in rules, rules
    print("SPMD-ANALYSIS-OK")
""")


def test_spmd_lint_and_replication_seeds():
    proc = subprocess.run(
        [sys.executable, "-c", SPMD_ANALYSIS_SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=REPO_ROOT, env=subprocess_env())
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SPMD-ANALYSIS-OK" in proc.stdout


J005_COVERAGE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.analysis import jaxpr_lint as JL

    counts = {ep.label: len(JL.unverified_shard_maps(ep.trace()))
              for ep in JL.spmd_entry_points()}
    # The Pallas and tol>0 SPMD paths turn JAX's own check off, so J005
    # is their only replication check; it must actually see them.
    unchecked = [k for k in counts if "pallas" in k or "tol>0" in k]
    assert unchecked and all(counts[k] == 1 for k in unchecked), counts
    assert sum(counts.values()) == len(unchecked), counts
    print("J005-COVERAGE-OK", sum(counts.values()))
""")


def test_replication_pass_inspects_live_shard_maps():
    """J005 analyzes only shard_maps traced with check_vma=False; a JAX
    upgrade that renames that parameter would silently leave it nothing
    to inspect, so the live SPMD programs must each give it one."""
    proc = subprocess.run(
        [sys.executable, "-c", J005_COVERAGE_SCRIPT],
        capture_output=True, text=True, timeout=600,
        cwd=REPO_ROOT, env=subprocess_env())
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "J005-COVERAGE-OK" in proc.stdout


# ---------------------------------------------------------------------------
# conventions: live repo clean + one seeded violation per rule
# ---------------------------------------------------------------------------
def test_live_conventions_clean():
    paths = [os.path.join(REPO_ROOT, p)
             for p in ("src", "tests", "benchmarks")]
    findings = conventions.run_pass(paths, repo_root=REPO_ROOT)
    assert findings == [], render_report(findings)


def _lint_src(source, filename="seed.py", tmp_path=None):
    path = filename if tmp_path is None else str(tmp_path / filename)
    return [f.rule for f in conventions.lint_file(
        path, source=source,
        repo_root=None if tmp_path is None else str(tmp_path))]


def test_seeded_missing_backend_detected():
    src = "def solve_batched(packed, num_iters):\n    return None\n"
    assert _lint_src(src) == ["R001"]


def test_seeded_tracer_cast_detected():
    src = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            m = int(num_iters)            # bare name: static arg, exempt
            v = float(jnp.sum(x))         # tracer cast: flagged
            w = x.max().item()            # device sync: flagged
            k = int(x.shape[0])           # static metadata: exempt
            return v + w + m + k
    """)
    assert _lint_src(src) == ["R002", "R002"]


def test_seeded_tracer_cast_outside_jit_clean():
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def host_loop(x):
            return float(jnp.max(x))      # not a jit context
    """)
    assert _lint_src(src) == []


def test_seeded_tight_rtol_without_x64_detected(tmp_path):
    src = textwrap.dedent("""
        import numpy as np

        def test_parity():
            np.testing.assert_allclose(1.0, 1.0, rtol=1e-9)
    """)
    assert _lint_src(src, "test_seed.py", tmp_path) == ["R003"]
    fixed = 'import jax\njax.config.update("jax_enable_x64", True)\n' + src
    assert _lint_src(fixed, "test_seed.py", tmp_path) == []
    # an ancestor conftest enabling x64 also satisfies the rule
    (tmp_path / "conftest.py").write_text(
        'import jax\njax.config.update("jax_enable_x64", True)\n')
    assert _lint_src(src, "test_seed.py", tmp_path) == []


def test_seeded_raw_interpret_detected():
    src = textwrap.dedent("""
        from repro.kernels.rff_gram import rff_gram_pallas

        def direct(a, b, x, y, m):
            return rff_gram_pallas(a, b, x, y, m, scale=1.0,
                                   block_n=128, interpret=True)
    """)
    assert _lint_src(src) == ["R004"]


def test_seeded_bare_except_detected():
    src = "try:\n    pass\nexcept:\n    pass\n"
    assert _lint_src(src) == ["R005"]
    waived = "try:\n    pass\nexcept:  # analysis: ignore[R005]\n    pass\n"
    assert _lint_src(waived) == []


# ---------------------------------------------------------------------------
# report + CLI
# ---------------------------------------------------------------------------
def test_report_rendering():
    import json

    fs = [Finding("vmem", "V001", "k", "over budget"),
          Finding("jaxpr", "J005", "ep", "not provably replicated",
                  severity="warning")]
    doc = json.loads(render_json(fs))
    assert doc["num_errors"] == 1 and doc["num_warnings"] == 1
    assert doc["findings"][0]["rule"] == "V001"
    text = render_report(fs)
    assert "[V001]" in text and "[J005]" in text
    assert "clean" in render_report([])


def test_cli_conventions_json():
    import json

    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--pass", "conventions",
         "--format", "json", "src", "tests", "benchmarks"],
        capture_output=True, text=True, timeout=300,
        cwd=REPO_ROOT, env=subprocess_env())
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["num_errors"] == 0
    assert "conventions" in doc["timings_s"]
