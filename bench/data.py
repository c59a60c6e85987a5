"""The benchmark's own copy of the twitter stand-in and its partitioners.

Copied from the program's synthetic data module so that the inputs stay
fixed while the program changes. `tests/bench/test_bench_data.py` checks
that this copy reproduces the program's generator bit for bit.

The stand-in keeps the paper's (d, N) for twitter (Tab. 1) and draws a
smooth, spatially modulated teacher plus heteroscedastic noise. x is
scaled to [0, 1], y to [-1, 1]. Layout: x is [d, N], columns are samples.
"""
from __future__ import annotations

import hashlib

import numpy as np

DATASET_SPECS: dict[str, tuple[int, int]] = {
    # name: (d, N) from the paper's Tab. 1
    "twitter": (77, 98704),
}


def make_dataset(name: str, *, seed: int = 0, subsample: int | None = None,
                 noise: float = 0.05, teacher_features: int = 64,
                 teacher_components: int = 4
                 ) -> tuple[np.ndarray, np.ndarray]:
    """(x [d, N] float64 in (0, 1), y [N] float64 in [-1, 1])."""
    if name not in DATASET_SPECS:
        raise KeyError(f"unknown dataset {name!r}; have {list(DATASET_SPECS)}")
    d, n = DATASET_SPECS[name]
    if subsample is not None:
        n = min(n, subsample)
    name_seed = int.from_bytes(hashlib.md5(name.encode()).digest()[:4],
                               "little")
    rng = np.random.default_rng(seed + name_seed % (2**31))

    mix = rng.normal(size=(d, d)) / np.sqrt(d)
    raw = mix @ rng.normal(size=(d, n)) + 0.3 * rng.normal(size=(d, n))
    x = 1.0 / (1.0 + np.exp(-raw))

    m = teacher_components
    gate_w = rng.normal(size=(m, d)) * 3.0 / np.sqrt(d)
    gate_b = rng.normal(size=(m, 1))
    logits = gate_w @ (x - 0.5) + gate_b
    logits -= logits.max(axis=0, keepdims=True)
    gates = np.exp(logits)
    gates /= gates.sum(axis=0, keepdims=True)

    sigmas = np.geomspace(0.25 * np.sqrt(d), 2.0 * np.sqrt(d), m)
    f = np.zeros(n)
    for c in range(m):
        omega = rng.normal(size=(teacher_features, d)) / sigmas[c]
        bias = rng.uniform(0, 2 * np.pi, size=(teacher_features, 1))
        coef = rng.normal(size=teacher_features) / np.sqrt(teacher_features)
        f += gates[c] * (coef @ np.cos(omega @ x + bias))

    scale = noise * (1.0 + np.linalg.norm(x, axis=0) / np.sqrt(d))
    y = f + rng.normal(size=n) * scale
    y = 2.0 * (y - y.min()) / max(y.max() - y.min(), 1e-12) - 1.0
    return x.astype(np.float64), y.astype(np.float64)


def equal_sizes(n: int, num_nodes: int) -> list[int]:
    base = n // num_nodes
    sizes = [base] * num_nodes
    for i in range(n - base * num_nodes):
        sizes[i] += 1
    return sizes


def imbalanced_sizes(n: int, num_nodes: int) -> list[int]:
    """Paper §IV-B2: N_j = (2j−1)/J² · N (for J = 10: (2j−1)N/100)."""
    weights = np.array([2 * j - 1 for j in range(1, num_nodes + 1)], float)
    weights /= weights.sum()
    sizes = np.floor(weights * n).astype(int)
    sizes[-1] += n - sizes.sum()
    return sizes.tolist()


def partition_indices(y: np.ndarray, sizes: list[int], mode: str, *,
                      seed: int = 0) -> list[np.ndarray]:
    """Sample indices per node. mode "iid": a seeded permutation dealt out
    contiguously; "noniid_y": sort |y| descending, dealt out contiguously."""
    n = y.shape[0]
    if sum(sizes) != n:
        raise ValueError(f"sizes sum {sum(sizes)} != N {n}")
    rng = np.random.default_rng(seed)
    if mode == "iid":
        order = rng.permutation(n)
    elif mode == "noniid_y":
        order = np.argsort(-np.abs(y))
    else:
        raise ValueError(f"unknown partition mode {mode!r}")
    out, start = [], 0
    for s in sizes:
        out.append(order[start:start + s])
        start += s
    return out


def train_test_split(shards: list[np.ndarray], *, seed: int = 0
                     ) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """Each node trains on half its local samples and tests on the rest."""
    rng = np.random.default_rng(seed)
    train, test = [], []
    for idx in shards:
        perm = rng.permutation(idx.shape[0])
        half = idx.shape[0] // 2
        train.append(idx[perm[:half]])
        test.append(idx[perm[half:]])
    return train, test


def node_shards(y: np.ndarray, num_nodes: int, partition: str, sizes: str,
                *, seed: int = 0) -> tuple[list[np.ndarray], list[np.ndarray]]:
    """The deployment's per-node (train, test) sample indices.

    partition: "iid" | "noniid_y"; sizes: "equal" | "imbalanced"."""
    n = y.shape[0]
    if sizes == "equal":
        counts = equal_sizes(n, num_nodes)
    elif sizes == "imbalanced":
        counts = imbalanced_sizes(n, num_nodes)
    else:
        raise ValueError(f"unknown shard sizes {sizes!r}")
    return train_test_split(partition_indices(y, counts, partition,
                                              seed=seed), seed=seed)
