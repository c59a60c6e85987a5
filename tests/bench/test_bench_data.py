"""The benchmark's copy of the data generator matches the program's."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import data as bdata  # noqa: E402
from repro.data import synthetic  # noqa: E402


@pytest.mark.parametrize("seed", [0, 1])
def test_bench_data_matches_program_generator(seed):
    ds = synthetic.make_dataset("twitter", seed=seed)
    x, y = bdata.make_dataset("twitter", seed=seed)
    assert x.shape == (77, 98704)
    np.testing.assert_array_equal(x, ds.x)
    np.testing.assert_array_equal(y, ds.y)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("mode,sizes", [("noniid_y", "equal"),
                                        ("iid", "imbalanced")])
def test_bench_partitions_match_program(seed, mode, sizes):
    ds = synthetic.make_dataset("twitter", seed=seed, subsample=3000)
    counts = (synthetic.imbalanced_sizes(ds.num_samples, 10)
              if sizes == "imbalanced" else None)
    nodes = synthetic.partition(ds, 10, mode=mode, sizes=counts, seed=seed)
    train, test = synthetic.train_test_split_nodes(nodes, seed=seed)
    tr_idx, te_idx = bdata.node_shards(ds.y, 10, mode, sizes, seed=seed)
    for want, idx in zip(train + test, tr_idx + te_idx):
        np.testing.assert_array_equal(np.asarray(want.x), ds.x[:, idx])
        np.testing.assert_array_equal(np.asarray(want.y), ds.y[idx])
