"""Static verification of solver programs — the hazards each pass guards.

The repo's correctness contract is otherwise enforced only dynamically:
a too-large ``D_max`` dies as an opaque Mosaic allocation crash, an
out-of-range slot-table index silently reads an arbitrary θ row through
scalar prefetch, and a stray ``float()`` on a tracer re-introduces the
per-round host syncs the fused solve removed. The three passes here make
those contracts static, checked on every CI push and pinned by
``tests/test_analysis.py``:

``jaxpr_lint`` — traces every solver entry point (``solve_batched``,
  ``async_solve_batched``, the shard_map SPMD solvers, the
  ``ops.dekrr_step``/``ops.dekrr_solve`` wrappers, and
  ``StreamingDeKRR.ingest``) to a closed jaxpr and verifies, per rule:

  J001  no host callbacks inside ``while``/``scan`` bodies — a callback
        in the solve loop serializes every round on the host;
  J002  ``pallas_call`` dispatch counts match the documented
        ``round_dispatches`` contract per backend (the fused kernel's
        whole reason to exist is dispatches=1);
  J003  every ``ppermute`` permutation is a bijection over the mesh
        axis — a dropped or duplicated edge deadlocks or corrupts the
        halo exchange;
  J004  loop carries never silently downcast f64→f32 — the rtol-1e-9
        parity contract dies quietly otherwise;
  J005  operands and control flow feeding collectives under
        ``check_vma=False`` are provably replicated — a device-varying
        ``while`` predicate gating a collective is a deadlock
        (the async mask-schedule hazard).

``vmem`` — executable versions of the four Pallas kernels' VMEM
  working-set formulas (consolidated table in the module docstring).
  The ``kernels/ops.py`` wrappers call these before dispatch so an
  over-budget ``(J, D_max, K)`` raises ``VmemBudgetError`` naming the
  formula and the 16 MiB limit instead of a Mosaic crash (rule V001),
  and the jaxpr lint re-budgets every traced ``pallas_call`` from its
  BlockSpecs (rule V002). Also hosts ``check_index_table`` — the static
  bounds check for scalar-prefetched slot/activation tables (scalar
  prefetch has no hardware bounds check).

``conventions`` — AST linter for the house contracts (rules R001–R005):
  solver entry points expose ``backend=``; no ``.item()``/``float()``/
  ``int()`` on tracers in jitted code; rtol ≤ 1e-6 tests enable x64;
  Pallas ``interpret=`` only through the ops wrappers; no bare
  ``except``.

Run all passes with ``python -m repro.analysis`` (text or ``--format
json``). This package root imports neither jax nor the jaxpr pass — the
CLI must configure ``JAX_PLATFORMS``/host-device-count env vars before
jax is first imported, and the conventions/vmem passes are useful in
environments with no accelerator runtime at all.
"""
from repro.analysis.report import (Finding, render_json,  # noqa: F401
                                   render_report)
from repro.analysis.vmem import (VMEM_BUDGET_BYTES,  # noqa: F401
                                 VmemBudgetError, VmemEstimate,
                                 check_index_table, effective_itemsize,
                                 estimate_blocks,
                                 estimate_dekrr_async_solve,
                                 estimate_dekrr_cheb_solve,
                                 estimate_dekrr_solve, estimate_dekrr_step,
                                 estimate_flash_decode,
                                 estimate_rff_features,
                                 estimate_rff_gram)

__all__ = [
    "Finding", "render_json", "render_report",
    "VMEM_BUDGET_BYTES", "VmemBudgetError", "VmemEstimate",
    "check_index_table", "effective_itemsize", "estimate_blocks",
    "estimate_dekrr_step", "estimate_dekrr_solve",
    "estimate_dekrr_async_solve", "estimate_dekrr_cheb_solve",
    "estimate_rff_gram", "estimate_rff_features",
    "estimate_flash_decode",
]
