"""Pallas TPU kernels for the framework's compute hot-spots.

* rff_gram.py        — fused RFF featurize + streaming Gram (the paper's
                       Eq. 17 pre-iteration hot-spot; features never leave
                       VMEM), per node: a ragged solver's `gram_fn`
* rff_features.py    — fused featurize for the cross-feature exchange
* dekrr_step.py      — fused packed Eq. 19 round for all J nodes (slot-table
                       neighbor gather + Σ P θ reduction + G GEMM, θ
                       VMEM-resident; the `repro.dist` backend="pallas" path)
* dekrr_solve.py     — fused MULTI-round Eq. 19 solve: the whole lax.scan in
                       one pallas_call, grid (rounds, nodes), two VMEM θ
                       tables alternating by round parity (the `repro.dist`
                       backend="pallas_fused" path)
* decode_attention.py— flash-decode for the serving path (§Perf pair 2)

ops.py holds the jit'd public wrappers (padding/alignment, backend
dispatch: interpret=True on non-TPU backends); ref.py the pure-jnp
oracles every kernel is allclose-tested against.
"""
from repro.kernels import ops
from repro.kernels.ops import (dekrr_solve, dekrr_step, flash_decode,
                               gram_fn_for_solver, rff_features, rff_gram)

__all__ = ["dekrr_solve", "dekrr_step", "flash_decode", "gram_fn_for_solver",
           "ops", "rff_features", "rff_gram"]
