"""Production meshes and logical-axis rule tables.

Single pod: (data=16, model=16) = 256 chips.  Multi-pod: (pod=2, data=16,
model=16) = 512 chips; the "pod" axis composes as an outer data-parallel axis
whose collectives ride the DCN (gradient all-reduce only — weights and
optimizer state shard over the intra-pod axes, keeping the DCN quiet).

Defined as FUNCTIONS so importing this module never touches jax device state
(the dry-run sets XLA_FLAGS before its first jax call).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple, Union

import numpy as np
import jax
from jax.sharding import AxisType, Mesh

MeshAxis = Union[None, str, Tuple[str, ...]]


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
               devices=None) -> Mesh:
    """``jax.make_mesh`` defaults to Explicit axes; every solver path
    (``shard_map`` bodies, partition gathers/scatters) is written for Auto
    sharding propagation, so every mesh of this repo is built here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = jax.device_count()
    if model_axis < 1 or n % model_axis != 0:
        raise ValueError(
            f"model_axis={model_axis} must be a positive divisor of the "
            f"device count ({n}); force more host devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N")
    return _auto_mesh((n // model_axis, model_axis), ("data", "model"))


def make_conquer_mesh(axis: str = "shard", devices=None) -> Mesh:
    """Flat 1-axis mesh over ``devices`` (default: every local device) — the
    layout the distributed DC-SVM divide/conquer runs on (rows of the dual
    sharded over ``axis``)."""
    devices = list(jax.devices() if devices is None else devices)
    return _auto_mesh((len(devices),), (axis,), devices=devices)


def rules_for(mesh: Mesh) -> Dict[str, MeshAxis]:
    """Logical-axis -> mesh-axis table (see models/param.py).

    batch   -> all data-like axes (pod + data)
    embed   -> "data"  (2D weight sharding: the FSDP-like dim)
    heads/mlp/vocab/expert -> "model" (the TP/EP dim)
    layers  -> never sharded (scan axis)
    """
    has_pod = "pod" in mesh.axis_names
    return {
        "batch": ("pod", "data") if has_pod else ("data",),
        "embed": "data",
        "heads": "model",
        "mlp": "model",
        "vocab": "model",
        "expert": "model",
        "layers": None,
        # KV caches shard their SEQUENCE dim over the model axis: works for
        # any kv-head count (GQA kv=8 and MQA kv=1 cannot shard 16-way), and
        # decode's softmax/weighted-sum reduce over the shards with tiny
        # per-token collectives instead of moving the cache (§Perf H8)
        "kv_seq": "model",
    }


def flat_axis_size(mesh: Mesh, axes: MeshAxis) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))
