"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

Functions, not module constants: importing this module never touches jax
device state (jax locks the device count on first backend init).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType



def _mk(shape, axes):
    n = math.prod(shape)
    devs = jax.devices()[:n]
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devs)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax (launch/dryrun.py does this)")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh for tests / elastic scaling."""
    return _mk(tuple(shape), tuple(axes))


def elastic_mesh(*, model_parallel: int = 16):
    """Derive a mesh from whatever devices exist (elastic scaling): model
    axis fixed at ``model_parallel``, everything else data-parallel."""
    n = jax.device_count()
    mp = math.gcd(model_parallel, n)
    return _mk((n // mp, mp), ("data", "model"))
