"""Production meshes.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  512 chips as (pod=2, data=16, model=16) — the `pod` axis carries
data parallelism across the inter-pod (DCN/ICI-extended) links; parameters
FSDP over (pod, data).

Defined as functions so importing this module never touches jax device
state (the dry-run forces a 512-device host platform *before* jax init).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False,
                         data: int = 16, model: int = 16):
    """(data, model) default to the production 16×16 pod; smoke tests pass a
    smaller grid (e.g. 4×4) to exercise the identical SPMD pipeline cheaply."""
    shape = (2, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_abstract_mesh(shape, axes):
    """Device-free AbstractMesh of the given axis sizes and names."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))


def make_cpu_mesh(num_devices: int | None = None, axis: str = "nodes"):
    """1-D mesh over however many (host) devices exist — used by the
    decentralized DeKRR runtime."""
    import numpy as np

    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    from jax.sharding import Mesh
    return Mesh(np.array(devs), (axis,))


# TPU v5e hardware constants (roofline; per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BANDWIDTH = 819e9           # B/s
ICI_LINK_BANDWIDTH = 50e9       # B/s per link
