"""Production mesh construction.

Single pod: 256 chips as (16 data, 16 model).  Multi-pod: 2 pods x 256 =
512 chips as (2 pod, 16 data, 16 model); the ``pod`` axis carries either
data parallelism (training: hierarchical gradient reduction) or the
prefill/decode disaggregation boundary (serving: XDT cache pulls are the
only traffic that crosses it).

Functions, not module-level constants: importing this module never touches
jax device state (required so smoke tests see 1 device while the dry-run
sees 512).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = 1
    for s in shape:
        ndev *= s
    devices = jax.devices()[:ndev]
    if len(devices) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for the production mesh, have {len(devices)} "
            "(the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512)"
        )
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1, pod: Optional[int] = None) -> Mesh:
    """Small mesh over whatever local devices exist (tests / examples)."""
    shape = (pod, data, model) if pod else (data, model)
    axes = ("pod", "data", "model") if pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * len(axes))
