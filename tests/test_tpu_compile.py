"""Compile every DeKRR/RFF Pallas kernel for a TPU v5e — no chip needed.

The TPU compiler (Mosaic) is installed with jaxlib and compiles for a
*described* topology. It refuses what interpret mode accepts: block shapes
whose last two dims are neither (8, 128)-aligned nor the array's own,
unaligned dynamic sublane slices, unsupported dot precisions. So these
tests compile each kernel through its `repro.kernels.ops` wrapper at the
widths of the paper's Table 2 deployment (J = 10 nodes, D̄ = 130 features
padded to D = 256, K = 4 circulant slots, f32), for scalar (Dy = 1) and
multi-output (Dy = 3) targets, with ``interpret=False`` passed explicitly
(the default picks interpret mode off-TPU).

The topology is described inside a module-scoped fixture and nowhere
else: only one process may hold the TPU library, so describing it while
a module is imported would break multi-worker test runs.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

J, D, K, ROUNDS = 10, 256, 4, 32
D_IN, N_NODE = 77, 4935                 # twitter: d = 77, N_j at J = 10
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(sharding, fn, *shapes):
    """Compile `fn` for the described chip; returns the optimized HLO.
    x64 is off, as on the chip path: the CPU suite's x64 mode would make
    the kernels' index arithmetic 64-bit, which Mosaic does not lower."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*args).compile().as_text()


def _dekrr_case(kernel: str, dy: int):
    """(fn, operand shapes) for one DeKRR kernel at the smoke widths."""
    rows = (J, D) if dy == 1 else (J, D, dy)
    blocks = [((J, D, D), F32), (rows, F32), ((J, D, D), F32),
              ((J, K, D, D), F32), (rows, F32)]
    slots = [((J, K), I32), ((J,), I32), ((J, K), I32)]
    if kernel == "dekrr_step":
        return (lambda g, d, s, p, th, ni, si, nm: ops.dekrr_step(
            g, d, s, p, th, ni, si, nm, interpret=False),
            blocks + slots)
    if kernel == "dekrr_step_masked":
        return (lambda g, d, s, p, th, ni, si, nm, act: ops.dekrr_step(
            g, d, s, p, th, ni, si, nm, act, interpret=False),
            blocks + slots + [((J,), I32)])
    if kernel == "dekrr_solve":
        return (lambda g, d, s, p, th, ni, si, nm: ops.dekrr_solve(
            g, d, s, p, th, ni, si, nm, num_rounds=ROUNDS, trace=True,
            interpret=False),
            blocks + slots)
    if kernel == "dekrr_async_solve":
        bufs = (J, K, D) if dy == 1 else (J, K, D, dy)
        return (lambda g, d, s, p, th, se, bu, ni, nm, act, thr:
                ops.dekrr_async_solve(
                    g, d, s, p, th, se, bu, ni, nm, act, thr,
                    censored=True, trace=True, interpret=False),
                blocks + [(rows, F32), (bufs, F32), ((J, K), I32),
                          ((J, K), I32), ((ROUNDS, J), I32),
                          ((ROUNDS,), F32)])
    if kernel == "dekrr_cheb_solve":
        return (lambda g, d, s, p, th, de, ni, si, nm, al, be:
                ops.dekrr_cheb_solve(
                    g, d, s, p, th, de, ni, si, nm, al, be, trace=True,
                    interpret=False),
                blocks + [(rows, F32)] + slots
                + [((ROUNDS,), F32), ((ROUNDS,), F32)])
    raise AssertionError(kernel)


@pytest.mark.parametrize("dy", [1, 3])
@pytest.mark.parametrize("kernel", ["dekrr_step", "dekrr_step_masked",
                                    "dekrr_solve", "dekrr_async_solve",
                                    "dekrr_cheb_solve"])
def test_dekrr_kernel_compiles_for_v5e(one_chip, kernel, dy):
    fn, shapes = _dekrr_case(kernel, dy)
    hlo = _compile(one_chip, fn, *shapes)
    assert hlo.count("tpu_custom_call") == 1, kernel


def test_rff_gram_compiles_for_v5e(one_chip):
    """The per-node Eq. 17 Gram kernel that a ragged solver takes as its
    `gram_fn` (`ops.gram_fn_for_solver`), at one node's Table 2 size."""
    hlo = _compile(
        one_chip,
        lambda om, b, x, y: ops.rff_gram(om, b, x, y, scale=0.124,
                                         interpret=False),
        ((130, D_IN), F32), ((130,), F32), ((D_IN, N_NODE), F32),
        ((N_NODE,), F32))
    assert hlo.count("tpu_custom_call") == 1


def test_rff_features_compiles_for_v5e(one_chip):
    """The serving featurize kernel at one 512-column query wave."""
    hlo = _compile(
        one_chip,
        lambda om, b, x: ops.rff_features(om, b, x, scale=0.124,
                                          interpret=False),
        ((130, D_IN), F32), ((130,), F32), ((D_IN, 512), F32))
    assert hlo.count("tpu_custom_call") == 1


def _f64_call(kernel: str):
    """A compiled-kernel call with float64 operands (x64 is on in tests)."""
    rng = np.random.default_rng(0)
    j, d, k, r = 2, 8, 1, 2
    f = lambda *shape: jnp.asarray(rng.normal(size=shape))       # float64
    idx = jnp.zeros((j, k), I32)
    self_idx = jnp.arange(j, dtype=I32)
    if kernel == "dekrr_step":
        return lambda: ops.dekrr_step(f(j, d, d), f(j, d), f(j, d, d),
                                      f(j, k, d, d), f(j, d), idx, self_idx,
                                      idx, interpret=False)
    if kernel == "dekrr_solve":
        return lambda: ops.dekrr_solve(f(j, d, d), f(j, d), f(j, d, d),
                                       f(j, k, d, d), f(j, d), idx,
                                       self_idx, idx, num_rounds=r,
                                       interpret=False)
    if kernel == "dekrr_async_solve":
        return lambda: ops.dekrr_async_solve(
            f(j, d, d), f(j, d), f(j, d, d), f(j, k, d, d), f(j, d),
            f(j, d), f(j, k, d), idx, idx, jnp.ones((r, j), I32), f(r),
            interpret=False)
    if kernel == "dekrr_cheb_solve":
        return lambda: ops.dekrr_cheb_solve(
            f(j, d, d), f(j, d), f(j, d, d), f(j, k, d, d), f(j, d),
            f(j, d), idx, self_idx, idx, f(r), f(r), interpret=False)
    if kernel == "rff_gram":
        return lambda: ops.rff_gram(f(d, 3), f(d), f(3, 16), f(16),
                                    scale=1.0, interpret=False)
    if kernel == "rff_features":
        return lambda: ops.rff_features(f(d, 3), f(d), f(3, 16), scale=1.0,
                                        interpret=False)
    raise AssertionError(kernel)


@pytest.mark.parametrize("kernel", ["dekrr_step", "dekrr_solve",
                                    "dekrr_async_solve", "dekrr_cheb_solve",
                                    "rff_gram", "rff_features"])
def test_compiled_kernel_refuses_float64(kernel):
    """The TPU has no f64: a compiled (interpret=False) kernel call with
    float64 operands raises at the wrapper, naming the dtype, instead of
    casting silently or failing inside Mosaic."""
    with pytest.raises(ValueError, match="float64"):
        _f64_call(kernel)()
