"""Data-dependent random feature (DDRF) selection.

Implements the two families the paper cites:

* energy / kernel-polarization score (Shahrampour et al., AAAI 2018 [33]):
  sample D0 candidate frequencies, score each by its alignment with the
  labels, keep the top-D. For a single cosine feature with bias,
      S(ω) = ( (1/N) Σ_i y_i ψ(ω, x_i) )²
  and for the paired cos/sin construction
      S(ω) = ( Σ_i y_i cos(ωᵀx_i) )² + ( Σ_i y_i sin(ωᵀx_i) )²  (scaled).
  This is the empirical estimate of E_{x,y}E_{x',y'}[y y' ψω(x) ψω(x')].

* ridge leverage scores (Li et al. 2021 [35]; Liu et al. 2020 [36]):
  with candidate feature matrix Φ ∈ R^{D0×N} (rows = features over data),
  the (primal, feature-space) ridge leverage of feature k is
      τ_k = [ Φ Φᵀ (Φ Φᵀ + λ N I)⁻¹ ]_{kk},
  computed from the D0×D0 Gram — O(D0² N + D0³). Features are then either
  taken top-D by τ or resampled with probability ∝ τ.

Because the scores are computed on *local* data, each node ends up with its
own feature map — the regime DeKRR-DDRF is designed for.
"""
from __future__ import annotations

from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.rff import FeatureMap, featurize, sample_rff
from repro.obs.spans import count, h2d_nbytes, is_recording, span


def _fold_paired(per_feature: jax.Array, fmap: FeatureMap) -> jax.Array:
    """Collapse per-feature values to per-frequency scores.

    A cos_sin map carries two feature channels per frequency ω (the cos row
    and the sin row, stacked [cos; sin]); both score families assign ω the
    SUM of its two channels' values. cos_bias maps are one channel per
    frequency, so this is the identity there. [num_features] →
    [num_frequencies].
    """
    if fmap.kind == "cos_sin":
        d = fmap.num_frequencies
        return per_feature[:d] + per_feature[d:]
    return per_feature


def _channels(fmap: FeatureMap, x: jax.Array) -> jax.Array:
    """Unscaled per-feature channel matrix [num_features, N]: the rows of
    the feature map before the 1/√D (or √(2/D)) normalization — the layout
    `_fold_paired` folds back to frequencies."""
    proj = fmap.omega @ x                              # [D, N]
    if fmap.kind == "cos_sin":
        return jnp.concatenate([jnp.cos(proj), jnp.sin(proj)], axis=0)
    return jnp.cos(proj + fmap.bias[:, None])


def energy_scores(fmap: FeatureMap, x: jax.Array, y: jax.Array) -> jax.Array:
    """Per-frequency energy score on data (X [d,N], Y [1,N] or [N]).

    Multi-output labels Y [N, Dy] score each frequency by the SUM of its
    per-output alignments — the natural extension of the polarization
    objective to a vector target (and identical to the scalar score at
    Dy=1)."""
    if y.ndim == 2 and y.shape[0] != 1:                # [N, Dy] labels
        n = y.shape[0]
        align = _channels(fmap, x) @ y                 # [num_features, Dy]
        return _fold_paired(jnp.sum(align**2, axis=1), fmap) / (n**2)
    y = y.reshape(-1)
    n = y.shape[0]
    align = _channels(fmap, x) @ y                     # [num_features]
    return _fold_paired(align**2, fmap) / (n**2)


def leverage_scores(fmap: FeatureMap, x: jax.Array,
                    lam: float = 1e-6) -> jax.Array:
    """Ridge leverage score per frequency (paired features are summed)."""
    return _leverage_scores(fmap, x, lam * x.shape[-1])


def _leverage_scores(fmap: FeatureMap, x: jax.Array, ridge) -> jax.Array:
    """`leverage_scores` with the ridge λN given whole: a traced ridge is
    then rounded once, as the host's product is, not formed in float32."""
    z = featurize(fmap, x)                             # [D_feat, N]
    g = z @ z.T                                        # [D_feat, D_feat]
    reg = ridge * jnp.eye(g.shape[0], dtype=g.dtype)
    # τ = diag(G (G + λN I)^{-1}) via Cholesky solve.
    sol = jax.scipy.linalg.cho_solve(
        jax.scipy.linalg.cho_factor(g + reg), g)
    return _fold_paired(jnp.diag(sol), fmap)


# Number of times the selection program has been *traced* (not called):
# the tests assert it does not grow over calls at one shape.
_SELECT_TRACE_COUNT = 0


def select_trace_count() -> int:
    return _SELECT_TRACE_COUNT


@partial(jax.jit, static_argnames=("dim", "num_features", "candidate_ratio",
                                   "method", "kind", "scorer"))
def _select(key, x, y, sigma, ridge, *, dim, num_features, candidate_ratio,
            method, kind, scorer) -> FeatureMap:
    """One node's selection as one program: the candidate draw, the
    scores, the top-D (or resampled) indices and the row gather. JAX's
    cache keys it on the shapes of x and y, so it compiles once per
    (D, N, d); σ and the ridge are traced and change nothing. `scorer` is
    `energy_scores` as the module holds it when called, static so that a
    replaced one (bench/faults.py plants one) gets a program of its own
    and not the cached one."""
    global _SELECT_TRACE_COUNT
    _SELECT_TRACE_COUNT += 1
    d0 = candidate_ratio * num_features
    k_cand, k_res = jax.random.split(key)
    cand = sample_rff(k_cand, dim, d0, sigma, kind=kind)
    if method == "energy":
        scores = scorer(cand, x, y)
    else:
        scores = _leverage_scores(cand, x, ridge)
    if method == "leverage_resample":
        p = jnp.maximum(scores, 0.0)
        p = p / jnp.sum(p)
        idx = jax.random.choice(k_res, d0, shape=(num_features,),
                                replace=False, p=p)
    else:
        idx = jnp.argsort(-scores)[:num_features]
    return cand.subset(idx)


def select_features(
    key: jax.Array,
    dim: int,
    num_features: int,
    sigma: float,
    x: jax.Array,
    y: jax.Array | None = None,
    *,
    method: Literal["plain", "energy", "leverage",
                    "leverage_resample"] = "energy",
    candidate_ratio: int = 20,
    kind: str = "cos_bias",
    leverage_lam: float = 1e-6,
) -> FeatureMap:
    """DDRF pipeline: sample D0 = ratio·D candidates, score, select D.

    ``method="plain"`` returns data-independent RFF (the DKLA setting).
    The paper follows [33] with D0/D = 20 (candidate_ratio). The other
    methods run as one compiled program per call (`_select`), which
    compiles once per shape of x and y and per number of features.
    """
    if method == "plain":
        return sample_rff(key, dim, num_features, sigma, kind=kind)

    if method not in ("energy", "leverage", "leverage_resample"):
        raise ValueError(f"unknown DDRF method {method!r}")
    if method == "energy" and y is None:
        raise ValueError("energy scoring requires labels y")
    d0 = candidate_ratio * num_features
    with span("ddrf.select", d0=d0, D=num_features, N=int(x.shape[-1])):
        if is_recording():      # numpy x and y are copied to the device
            count("ddrf.h2d_bytes", h2d_nbytes(x, y))
        return _select(key, x, y, sigma, leverage_lam * x.shape[-1],
                       dim=dim, num_features=num_features,
                       candidate_ratio=candidate_ratio, method=method,
                       kind=kind, scorer=energy_scores)
