"""Test configuration.

x64 is enabled for the paper-faithful numerics (KRR solves); all model-zoo
code uses explicit dtypes so this does not affect the transformer substrate.
Do NOT set XLA_FLAGS device-count here — smoke tests must see 1 device; only
launch/dryrun.py forces 512 placeholder devices (in its own process).

Cached problem builders: constructing a DeKRR problem (synthetic dataset →
non-IID split → per-node DDRF feature selection → O(J²) Eq. 17 aux build)
dominates the suite's runtime, and many parametrized cases rebuild identical
pieces. The `cached_*` helpers below memoize each stage on hashable keys
for the whole session; test modules import them directly
(`from conftest import cached_split`). Everything built from them is
treated as read-only by the tests.
"""
import functools
import os

import jax

jax.config.update("jax_enable_x64", True)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def subprocess_env(**extra: str) -> dict[str, str]:
    """Minimal env for tests that re-exec python with forced device counts.

    JAX_PLATFORMS=cpu is load-bearing: without it, a TPU-enabled jaxlib
    probes for TPU hardware (minutes of metadata-server retries) before
    falling back to CPU.
    """
    env = {
        "PYTHONPATH": "src",
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.environ.get("HOME", "/root"),
        "JAX_PLATFORMS": "cpu",
    }
    env.update(extra)
    return env


@functools.lru_cache(maxsize=None)
def cached_dataset(name: str, subsample: int, seed: int = 0):
    from repro.data.synthetic import make_dataset
    return make_dataset(name, subsample=subsample, seed=seed)


@functools.lru_cache(maxsize=None)
def cached_split(name: str, num_nodes: int, mode: str = "noniid_y",
                 subsample: int = 600, seed: int = 0):
    """(dataset, train, test) for a node-partitioned synthetic dataset."""
    from repro.data.synthetic import partition, train_test_split_nodes
    ds = cached_dataset(name, subsample, seed)
    train, test = train_test_split_nodes(
        partition(ds, num_nodes, mode=mode))
    return ds, train, test


@functools.lru_cache(maxsize=None)
def cached_fmaps(name: str, num_nodes: int, dims: tuple,
                 sigma: float = 1.0, method: str = "energy",
                 candidate_ratio: int = 5, mode: str = "noniid_y",
                 subsample: int = 600, seed: int = 0,
                 split_seed: int | None = None):
    """Per-node DDRF feature maps for a cached split (dims: one D_j each).

    `seed` drives the feature draw; the dataset/split uses `split_seed`
    (defaults to `seed`). Pass `split_seed` explicitly when the caller's
    training data comes from a fixed split but the feature draw varies.
    """
    from repro.core import select_features
    if split_seed is None:
        split_seed = seed
    ds, train, _ = cached_split(name, num_nodes, mode=mode,
                                subsample=subsample, seed=split_seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), num_nodes)
    return [
        select_features(keys[j], ds.dim, dims[j], sigma, train[j].x,
                        train[j].y, method=method,
                        candidate_ratio=candidate_ratio)
        for j in range(num_nodes)
    ]


@functools.lru_cache(maxsize=None)
def cached_imbalanced(name: str, num_nodes: int, dbar: int,
                      subsample: int = 600, seed: int = 0):
    """(dataset, train, fmaps) in the paper's Fig. 3 layout at a small
    size: N_j = (2j−1)/J²·N dealt iid, and energy DDRF maps of
    D_j = √N_j·J·D̄/Σ√N_i features (rounded, at least 4), so that both the
    sample and the feature counts differ from node to node."""
    import numpy as np

    from repro.core import select_features
    from repro.data.synthetic import (imbalanced_sizes, partition,
                                      train_test_split_nodes)
    ds = cached_dataset(name, subsample, seed)
    train, _ = train_test_split_nodes(partition(
        ds, num_nodes, mode="iid",
        sizes=imbalanced_sizes(ds.num_samples, num_nodes), seed=seed))
    w = np.sqrt([t.num_samples for t in train])
    dims = np.maximum((w * num_nodes * dbar / w.sum()).round(), 4)
    keys = jax.random.split(jax.random.PRNGKey(seed), num_nodes)
    fmaps = [select_features(keys[j], ds.dim, int(dims[j]), 1.0, train[j].x,
                             train[j].y, candidate_ratio=5)
             for j in range(num_nodes)]
    return ds, train, fmaps
