"""The reference draws a node's DDRF candidates from its key exactly as
the program does (`repro.core.ddrf.select_features`): the check matches
the program's selected rows against that draw, so the mapping from key to
candidates is part of what the program guarantees."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402,F401  (puts the repo and src on the path)


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_bench_reference_candidates_are_the_programs(seed):
    import jax

    from bench import check, reference as R
    from bench.registry import seed32
    from repro.core import select_features
    from repro.core.ddrf import sample_rff

    dim, width, ratio, sigma = 7, 5, 20, 2.0
    key = jax.random.fold_in(jax.random.PRNGKey(seed32(seed)), 3)
    rng = np.random.default_rng(seed % 2**32)
    x = rng.standard_normal((dim, 64)).astype(np.float32)
    y = rng.standard_normal(64).astype(np.float32)
    with jax.enable_x64(False):
        omega, bias = R.draw_candidates(key, dim, ratio * width, sigma)
        cand = sample_rff(jax.random.split(key)[0], dim, ratio * width,
                          sigma)
        fmap = select_features(key, dim, width, sigma, x, y,
                               method="energy", candidate_ratio=ratio)
    np.testing.assert_array_equal(omega, np.asarray(cand.omega))
    np.testing.assert_array_equal(bias, np.asarray(cand.bias))
    idx = check.match_rows(fmap.omega, fmap.bias, omega, bias)
    assert np.all(idx >= 0) and len(set(idx.tolist())) == width
