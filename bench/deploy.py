"""A configuration's deployment: data, shards, widths and graph.

Built from the benchmark's own data copy, so the inputs do not move when
the program changes. The data and its split are fixed by the
configuration (`data_seed`); the run's `--seed` draws the DDRF keys, the
queries and their arrivals.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench import data
from bench.registry import adjacency_of, feature_widths


@dataclasses.dataclass
class Deployment:
    x_train: list[np.ndarray]      # [d, N_j] float32 per node
    y_train: list[np.ndarray]      # [N_j] float32 per node
    x_test: list[np.ndarray]
    y_test: list[np.ndarray]
    widths: list[int]              # D_j
    adjacency: np.ndarray          # [J, J] bool
    config: dict

    @property
    def num_nodes(self) -> int:
        return len(self.widths)

    @property
    def dim(self) -> int:
        return int(self.x_train[0].shape[0])

    @property
    def num_train(self) -> int:
        return sum(int(x.shape[1]) for x in self.x_train)

    def neighbors(self, j: int) -> list[int]:
        return [int(p) for p in np.nonzero(self.adjacency[j])[0]]


def build(config: dict) -> Deployment:
    x, y = data.make_dataset(config["dataset"], seed=config["data_seed"],
                             subsample=config["num_samples"])
    if (x.shape[0], x.shape[1]) != (config["dim"], config["num_samples"]):
        raise ValueError(f"data is {x.shape}, the configuration states "
                         f"d={config['dim']}, N={config['num_samples']}")
    j_nodes = config["num_nodes"]
    train, test = data.node_shards(y, j_nodes, config["partition"],
                                   config["shard_sizes"],
                                   seed=config["data_seed"])
    f32 = lambda a: np.ascontiguousarray(a, dtype=np.float32)
    widths = feature_widths(config["feature_widths"], config["dbar"],
                            [len(i) for i in train])
    return Deployment(
        x_train=[f32(x[:, i]) for i in train], y_train=[f32(y[i]) for i in train],
        x_test=[f32(x[:, i]) for i in test], y_test=[f32(y[i]) for i in test],
        widths=widths, adjacency=adjacency_of(config["graph"], j_nodes),
        config=config)


def node_keys(key, i: int, num_nodes: int) -> list:
    """The DDRF keys of fit (or problem) i: node j draws from
    fold_in(fold_in(key, i), j)."""
    key = jax.random.fold_in(key, i)
    return [jax.random.fold_in(key, j) for j in range(num_nodes)]
