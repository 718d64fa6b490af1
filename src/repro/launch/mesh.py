"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). The dry-run forces 512 host devices via XLA_FLAGS before
any jax import; the single-pod mesh then uses the first 256.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import MeshConfig, MULTI_POD, SINGLE_POD


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(MeshConfig(shape, axes))


def make_mesh(cfg: MeshConfig) -> Mesh:
    n = cfg.n_devices
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {cfg.shape}, have {len(devices)} — "
            f"the dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=512 before importing jax")
    dev_array = np.asarray(devices[:n]).reshape(cfg.shape)
    return Mesh(dev_array, cfg.axes)


def make_replica_split_mesh(n_devices: int = 256) -> Mesh:
    """Single-pod mesh re-viewed for the paper's replication mode:
    (rep=2, data=8, model=16) — same 256 chips, the first `rep` slice is the
    computational group, the second is the replica group (DESIGN.md §4)."""
    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(f"need {n_devices} devices")
    dev_array = np.asarray(devices[:n_devices]).reshape(2, n_devices // 32, 16)
    return Mesh(dev_array, ("rep", "data", "model"))
