"""Work of a layer at the deployment's logical shapes, and the chip's peaks.

Counts are of what the algorithm needs, never of padded operands or of
what one implementation streams, so a roofline share reads the same work
whatever computes it. Flops count the multiply-adds of matrix products
(2 per multiply-add); elementwise work (cos, scaling) is not counted, so
a share errs low. Bytes count each input read once and each output
written once, in float32.
"""
from __future__ import annotations

import json
import os

F32 = 4


def peaks(device_kind: str, root: str) -> dict:
    """Peak flop/s and HBM bytes/s of `device_kind` from bench/peaks.json.
    A device missing from the table is an error."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; have "
                       f"{sorted(table)}")
    return table[device_kind]


def eq17_work(widths, sizes, dim: int, neighbors) -> tuple[float, float]:
    """(flops, bytes) of building the Eq. 17 blocks (G, d, S, P) of every
    node once.

    widths D_j, sizes N_j (training samples), dim d, neighbors[j] the
    node's neighbour list. Features Z_{i,k} are needed for i = k and for
    every directed edge (i, k); each is computed once."""
    flops = 0.0
    nbytes = 0.0
    pairs = {(j, j) for j in range(len(widths))}
    pairs |= {(j, p) for j in range(len(widths)) for p in neighbors[j]}
    for i, k in pairs:
        flops += 2.0 * widths[i] * dim * sizes[k]            # ωᵀx
    for j, (dj, nj) in enumerate(zip(widths, sizes)):
        flops += 2.0 * dj * dj * nj                           # Z_jj Z_jjᵀ
        flops += 2.0 * dj * nj                                # Z_jj y_j
        flops += 2.0 * dj ** 3                                # A_j⁻¹
        for p in neighbors[j]:
            flops += 2.0 * dj * dj * sizes[p]                 # Z_jp Z_jpᵀ
            flops += 2.0 * dj * widths[p] * (nj + sizes[p])   # P_jp
        nbytes += F32 * (dim * nj + nj + dj * (dim + 1))      # x, y, ω, b
        nbytes += F32 * (2 * dj * dj + dj)                    # G, S, d
        nbytes += F32 * sum(dj * widths[p] for p in neighbors[j])   # P
    return flops, nbytes


def serve_work(widths, dim: int, queries: int) -> tuple[float, float]:
    """(flops, bytes) of answering `queries` single-point queries with the
    network-average predictor: per node z_j(x) = √(2/D_j)cos(Ω_j x + b_j)
    and θ_jᵀz_j. Weights are read once; each query and answer once."""
    per_query = sum(2.0 * dj * dim + 2.0 * dj for dj in widths)
    weights = F32 * sum(dj * (dim + 2) for dj in widths)
    return per_query * queries, weights + F32 * (dim + 1) * queries


def least_seconds(flops: float, nbytes: float, peak: dict
                  ) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
