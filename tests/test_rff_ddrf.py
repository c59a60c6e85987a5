import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import ddrf
from repro.core.ddrf import (energy_scores, leverage_scores, select_features)
from repro.core.rff import (featurize, gaussian_kernel, sample_rff)


@pytest.mark.parametrize("kind", ["cos_sin", "cos_bias"])
def test_rff_approximates_gaussian_kernel(kind):
    key = jax.random.PRNGKey(0)
    d, n, D, sigma = 5, 40, 4096, 1.5
    x = jax.random.uniform(jax.random.PRNGKey(1), (d, n))
    fmap = sample_rff(key, d, D, sigma, kind=kind)
    z = featurize(fmap, x)
    k_hat = z.T @ z
    k_true = gaussian_kernel(x, x, sigma)
    err = jnp.max(jnp.abs(k_hat - k_true))
    assert err < 0.06, f"max kernel approx error {err}"


def test_cos_sin_has_double_features():
    fmap = sample_rff(jax.random.PRNGKey(0), 3, 10, 1.0, kind="cos_sin")
    assert fmap.num_features == 20
    z = featurize(fmap, jnp.zeros((3, 7)))
    assert z.shape == (20, 7)


@given(d=st.integers(1, 8), n=st.integers(1, 30), D=st.integers(1, 16),
       seed=st.integers(0, 2**16))
@settings(max_examples=25, deadline=None)
def test_featurize_shapes_and_norm_property(d, n, D, seed):
    """z(x)ᵀz(x) ≈ k(x,x) = 1 for the Gaussian kernel (unbiased in expectation,
    and exactly 1 for the cos_sin construction)."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (d, n))
    fmap = sample_rff(key, d, D, 1.0, kind="cos_sin")
    z = featurize(fmap, x)
    assert z.shape == (2 * D, n)
    diag = jnp.sum(z * z, axis=0)
    np.testing.assert_allclose(np.asarray(diag), 1.0, atol=1e-6)


def test_kernel_estimate_unbiased_monte_carlo():
    """Average of many independent D=1 estimates converges to k(x,x')."""
    d = 3
    x = jnp.array([[0.3], [0.1], [-0.2]])
    x2 = jnp.array([[-0.5], [0.4], [0.2]])
    k_true = float(gaussian_kernel(x, x2, 1.0)[0, 0])

    def one_estimate(key):
        fm = sample_rff(key, d, 4, 1.0, kind="cos_bias")
        return (featurize(fm, x) * featurize(fm, x2)).sum()

    keys = jax.random.split(jax.random.PRNGKey(42), 4000)
    ests = jax.vmap(one_estimate)(keys)
    assert abs(float(jnp.mean(ests)) - k_true) < 0.02


def test_energy_scores_prefer_signal_frequency():
    """Labels built from one known frequency → that frequency scores highest."""
    key = jax.random.PRNGKey(0)
    d, n = 4, 512
    x = jax.random.uniform(jax.random.PRNGKey(1), (d, n))
    omega_star = jnp.array([3.0, -2.0, 1.0, 0.5])
    y = jnp.cos(omega_star @ x + 0.7)
    fmap = sample_rff(key, d, 2000, 2.0, kind="cos_bias")
    # plant the true frequency among the candidates
    omega = fmap.omega.at[17].set(omega_star)
    bias = fmap.bias.at[17].set(0.7)
    planted = type(fmap)(omega=omega, bias=bias, kind=fmap.kind)
    scores = energy_scores(planted, x, y)
    assert int(jnp.argmax(scores)) == 17


def test_leverage_scores_in_unit_interval():
    key = jax.random.PRNGKey(0)
    x = jax.random.uniform(jax.random.PRNGKey(1), (6, 200))
    fmap = sample_rff(key, 6, 64, 1.0, kind="cos_bias")
    tau = leverage_scores(fmap, x, lam=1e-4)
    assert jnp.all(tau >= -1e-8) and jnp.all(tau <= 1.0 + 1e-8)


@pytest.mark.parametrize("method", ["plain", "energy", "leverage",
                                    "leverage_resample"])
def test_select_features_returns_requested_count(method):
    key = jax.random.PRNGKey(3)
    x = jax.random.uniform(jax.random.PRNGKey(4), (5, 128))
    y = jnp.sin(x.sum(axis=0))
    fmap = select_features(key, 5, 12, 1.0, x, y, method=method,
                           candidate_ratio=10)
    assert fmap.num_frequencies == 12
    assert featurize(fmap, x).shape == (12, 128)


def _eager_selection(key, dim, num_features, sigma, x, y, method, ratio,
                     kind, lam=1e-6):
    """The selection one eager op at a time: the candidate draw of the
    key's first half, the scores, top-D (or the resample on the second
    half), then the row gather. Returns (selected map, candidates)."""
    k_cand, k_res = jax.random.split(key)
    d0 = ratio * num_features
    cand = sample_rff(k_cand, dim, d0, sigma, kind=kind)
    x = jnp.asarray(x)
    if method == "energy":
        idx = jnp.argsort(-energy_scores(cand, x, jnp.asarray(y)))
        idx = idx[:num_features]
    else:
        scores = leverage_scores(cand, x, lam=lam)
        if method == "leverage":
            idx = jnp.argsort(-scores)[:num_features]
        else:
            p = jnp.maximum(scores, 0.0)
            p = p / jnp.sum(p)
            idx = jax.random.choice(k_res, d0, shape=(num_features,),
                                    replace=False, p=p)
    return cand.subset(idx), cand


@pytest.mark.parametrize("kind", ["cos_bias", "cos_sin"])
@pytest.mark.parametrize("method", ["energy", "leverage",
                                    "leverage_resample"])
def test_select_features_matches_eager_composition(method, kind):
    """The compiled selection returns the very rows the eager composition
    does (same draws, same scores, same tie order), and each is a row of
    the key's candidate draw."""
    rng = np.random.default_rng(11)
    d, n, D, ratio, sigma = 6, 240, 9, 12, 1.7
    x = rng.uniform(size=(d, n))
    y = np.sin(3.0 * x[0]) + x[1] ** 2
    for seed in (0, 1):
        key = jax.random.PRNGKey(40 + seed)
        got = select_features(key, d, D, sigma, x, y, method=method,
                              candidate_ratio=ratio, kind=kind)
        want, cand = _eager_selection(key, d, D, sigma, x, y, method, ratio,
                                      kind)
        assert got.kind == kind and got.omega.dtype == want.omega.dtype
        np.testing.assert_array_equal(np.asarray(got.omega),
                                      np.asarray(want.omega))
        if kind == "cos_bias":
            np.testing.assert_array_equal(np.asarray(got.bias),
                                          np.asarray(want.bias))
        else:
            assert got.bias is None
        om, co = np.asarray(got.omega), np.asarray(cand.omega)
        rows = [int(np.flatnonzero((co == r).all(axis=1))[0]) for r in om]
        assert len(set(rows)) == D
        if kind == "cos_bias":
            np.testing.assert_array_equal(np.asarray(got.bias),
                                          np.asarray(cand.bias)[rows])


def test_select_program_traced_once_per_shape():
    """New keys, data values and σ at one (D, N) reuse the compiled
    selection (a stream refresh's new σ̂ does not recompile); a new
    (D, N) traces it once more."""
    rng = np.random.default_rng(5)
    d, n = 5, 173

    def call(seed, num_features, n_cols, sigma):
        x = rng.normal(size=(d, n_cols))
        y = rng.normal(size=n_cols)
        return select_features(jax.random.PRNGKey(seed), d, num_features,
                               sigma, x, y, candidate_ratio=7)

    call(0, 11, n, 1.0)
    before = ddrf.select_trace_count()
    for seed, sigma in ((1, 1.0), (2, 0.37), (3, 2.9)):
        fmap = call(seed, 11, n, sigma)
        assert fmap.num_frequencies == 11
    assert ddrf.select_trace_count() == before
    call(4, 13, n + 6, 1.0)
    assert ddrf.select_trace_count() == before + 1
    call(5, 13, n + 6, 0.5)
    assert ddrf.select_trace_count() == before + 1


def test_ddrf_improves_over_plain_on_structured_target():
    """The paper's core premise: at equal D, energy-selected features fit a
    structured target better than data-independent RFF."""
    d, n, D, sigma, lam = 6, 800, 8, 1.0, 1e-6

    errs_plain, errs_ddrf = [], []
    for s in range(8):
        x = jax.random.uniform(jax.random.PRNGKey(s), (d, n))
        xe = jax.random.uniform(jax.random.PRNGKey(300 + s), (d, 400))
        omega_t = jax.random.normal(jax.random.PRNGKey(100 + s), (4, d)) * 1.5
        y = jnp.cos(omega_t @ x).sum(axis=0) / 4.0
        ye = jnp.cos(omega_t @ xe).sum(axis=0) / 4.0

        def fit_eval(fmap):
            z = featurize(fmap, x)
            g = z @ z.T + lam * n * jnp.eye(z.shape[0])
            th = jnp.linalg.solve(g, z @ y)
            pred = th @ featurize(fmap, xe)
            return float(jnp.mean((pred - ye) ** 2))

        k = jax.random.PRNGKey(200 + s)
        errs_plain.append(fit_eval(sample_rff(k, d, D, sigma)))
        errs_ddrf.append(fit_eval(select_features(
            k, d, D, sigma, x, y, method="energy", candidate_ratio=20)))
    assert np.mean(errs_ddrf) < np.mean(errs_plain)
