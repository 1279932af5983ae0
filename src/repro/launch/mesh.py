"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

:func:`make_data_mesh` builds the 1-D ("data",) mesh the sharded FCF round
engine runs on; :func:`fake_cpu_devices_env` prepares the environment for a
subprocess that should see N fake CPU devices (the only way to get a
multi-device CPU mesh — ``XLA_FLAGS`` must be set before the first jax
init, so tests and benchmarks spawn workers rather than re-init in place).
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: shardings come from the jit in/out specs and the models'
    # sharding hints, not from Explicit-axis types on every array
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_data_mesh(num_shards: Optional[int] = None) -> jax.sharding.Mesh:
    """1-D ("data",) mesh over the first ``num_shards`` local devices.

    The mesh of the sharded FCF round engine: (M, K) tables row-shard over
    "data", cohorts split one user block per device. ``None`` takes every
    visible device.
    """
    devices = jax.devices()
    d = len(devices) if num_shards is None else int(num_shards)
    if d < 1 or d > len(devices):
        raise ValueError(
            f"requested {num_shards} mesh devices, have {len(devices)}")
    return jax.sharding.Mesh(np.array(devices[:d]), ("data",))


_FAKE_CPU_FLAG = "--xla_force_host_platform_device_count"


def fake_cpu_devices_env(num_devices: int,
                         env: Optional[Dict[str, str]] = None
                         ) -> Dict[str, str]:
    """Environment for a subprocess that sees ``num_devices`` fake CPU devices.

    Appends ``--xla_force_host_platform_device_count=N`` to ``XLA_FLAGS``
    (dropping any previous setting of that flag). The flag only takes effect
    before the first jax initialization, hence the subprocess pattern used by
    ``tests/test_sharded_rounds.py`` and ``benchmarks/sharded_rounds.py``.
    ``JAX_PLATFORMS=cpu`` keeps the child off any accelerator: a chip belongs
    to one process at a time, and on a TPU host the parent may hold it.
    """
    env = dict(os.environ if env is None else env)
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith(_FAKE_CPU_FLAG)]
    kept.append(f"{_FAKE_CPU_FLAG}={int(num_devices)}")
    env["XLA_FLAGS"] = " ".join(kept)
    env["JAX_PLATFORMS"] = "cpu"
    return env


def batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """Mesh axes over which the global batch is sharded."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_axis_size(mesh: jax.sharding.Mesh) -> int:
    size = 1
    for ax in batch_axes(mesh):
        size *= mesh.shape[ax]
    return size
