"""Random Fourier features (Rahimi & Recht, 2007) for shift-invariant kernels.

Feature-matrix convention follows the paper: Z(X) ∈ R^{D_feat × N} with
columns z(x_i). Two real-valued constructions for the Gaussian kernel
k(x, x') = exp(-||x - x'||² / (2σ²)):

  cos_sin  (Eq. 9):  ψ(ω, x) = 1/√D [cos(ωᵀx); sin(ωᵀx)]      (D_feat = 2D)
  cos_bias (Eq. 10): ψ(ω, x) = √(2/D) cos(ωᵀx + b), b ~ U[0,2π) (D_feat = D)

The scale is folded into the feature map so that z(x)ᵀz(x') ≈ k(x, x').
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

# A TPU's default f32 matmul rounds operands to bf16, which moves a cos
# argument of |ωᵀx| ~ 10 by ~0.04 rad; featurize at full f32 precision.
_HIGHEST = jax.lax.Precision.HIGHEST


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class FeatureMap:
    """A concrete RFF map: frozen frequencies (and biases)."""

    omega: jax.Array          # [D, d]
    bias: jax.Array | None    # [D] for cos_bias, None for cos_sin
    kind: str                 # "cos_sin" | "cos_bias"

    # -- pytree plumbing (kind is static) ------------------------------------
    def tree_flatten(self):
        return (self.omega, self.bias), self.kind

    @classmethod
    def tree_unflatten(cls, kind, children):
        omega, bias = children
        return cls(omega=omega, bias=bias, kind=kind)

    @property
    def num_frequencies(self) -> int:
        return self.omega.shape[0]

    @property
    def num_features(self) -> int:
        d = self.omega.shape[0]
        return 2 * d if self.kind == "cos_sin" else d

    def __call__(self, x: jax.Array) -> jax.Array:
        """Featurize. x: [d, N] (paper layout) → Z: [num_features, N]."""
        return featurize(self, x)

    def subset(self, idx: jax.Array) -> "FeatureMap":
        """Select a subset of frequencies (DDRF top-D selection)."""
        return FeatureMap(
            omega=self.omega[idx],
            bias=None if self.bias is None else self.bias[idx],
            kind=self.kind,
        )


def sample_rff(key: jax.Array, dim: int, num_frequencies: int,
               sigma: float, kind: str = "cos_bias") -> FeatureMap:
    """Sample ω ~ N(0, σ⁻² I_d) (Gaussian kernel spectral density)."""
    if kind not in ("cos_sin", "cos_bias"):
        raise ValueError(f"unknown RFF kind {kind!r}")
    k_w, k_b = jax.random.split(key)
    omega = jax.random.normal(k_w, (num_frequencies, dim)) / sigma
    bias = None
    if kind == "cos_bias":
        bias = jax.random.uniform(k_b, (num_frequencies,), maxval=2 * jnp.pi)
    return FeatureMap(omega=omega, bias=bias, kind=kind)


@partial(jax.jit, static_argnames=())
def _featurize_cos_sin(omega: jax.Array, x: jax.Array) -> jax.Array:
    d = omega.shape[0]
    proj = jnp.matmul(omega, x, precision=_HIGHEST)    # [D, N]
    scale = jnp.asarray(1.0 / jnp.sqrt(d), proj.dtype)
    return jnp.concatenate([jnp.cos(proj), jnp.sin(proj)], axis=0) * scale


@partial(jax.jit, static_argnames=())
def _featurize_cos_bias(omega: jax.Array, bias: jax.Array,
                        x: jax.Array) -> jax.Array:
    d = omega.shape[0]
    proj = jnp.matmul(omega, x, precision=_HIGHEST) + bias[:, None]
    scale = jnp.sqrt(jnp.asarray(2.0 / d, proj.dtype))
    return jnp.cos(proj) * scale


def featurize(fmap: FeatureMap, x: jax.Array) -> jax.Array:
    """Z(X) ∈ R^{D_feat × N} for X ∈ R^{d × N}."""
    if x.ndim != 2:
        raise ValueError(f"x must be [d, N], got {x.shape}")
    if fmap.kind == "cos_sin":
        return _featurize_cos_sin(fmap.omega, x)
    return _featurize_cos_bias(fmap.omega, fmap.bias, x)


def gaussian_kernel(x: jax.Array, x2: jax.Array, sigma: float) -> jax.Array:
    """Exact Gaussian Gram matrix K ∈ R^{N×M} for X [d,N], X2 [d,M]."""
    sq = (
        jnp.sum(x * x, axis=0)[:, None]
        + jnp.sum(x2 * x2, axis=0)[None, :]
        - 2.0 * x.T @ x2
    )
    return jnp.exp(-jnp.maximum(sq, 0.0) / (2.0 * sigma**2))
