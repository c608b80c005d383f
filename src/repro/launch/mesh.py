"""Production mesh construction.

Defined as functions (not module constants) so importing never touches
jax device state — smoke tests must keep seeing 1 CPU device; only
dryrun.py sets XLA_FLAGS for 512 placeholder devices before any import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(n: int) -> dict:
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod slice: 16×16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: 'data' carries batch + federated clients + expert parallelism;
    'model' is tensor parallel; 'pod' is the cross-silo boundary (only
    adapter aggregation crosses it).  With 512 placeholder devices the
    single-pod mesh uses the first 256.
    """
    import numpy as np
    from jax.sharding import Mesh

    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == n:
        return jax.make_mesh(shape, axes, **_auto(len(axes)))
    assert len(devs) >= n, f"need {n} devices, have {len(devs)}"
    return Mesh(np.asarray(devs[:n]).reshape(shape), axes,
                **_auto(len(axes)))


def make_debug_mesh(n_data: int = 4, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for CI-scale distributed tests (8 host devices)."""
    if multi_pod:
        return jax.make_mesh((2, n_data // 2, n_model),
                             ("pod", "data", "model"), **_auto(3))
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         **_auto(2))


def make_client_mesh(n_clients: int):
    """Data-only mesh: one shard per federated client, no tensor-parallel
    axis.  Because every mesh axis is a client axis, the federated train
    step's ``jax.shard_map`` region is *fully* manual over it.  The CI
    --dist lane, the 8-virtual-device parity sweep and the chip smoke run
    on this mesh."""
    return jax.make_mesh((n_clients,), ("data",), **_auto(1))


def data_axes(mesh) -> tuple[str, ...]:
    from repro.utils.sharding import data_axis_names
    return data_axis_names(mesh)


def dp_size(mesh) -> int:
    out = 1
    for a in data_axes(mesh):
        out *= mesh.shape[a]
    return out

