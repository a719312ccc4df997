"""Production mesh construction.

A function (not a module constant) so importing never touches jax device
state. Single pod: (data=16, model=16) = 256 chips; multi-pod adds a
leading pure-DP "pod" axis: (pod=2, data=16, model=16) = 512 chips. The
dry-run launcher forces 512 host devices *before* any jax import.
"""
from __future__ import annotations

import math

import numpy as np

import jax
from jax.sharding import AxisType


def _auto(n: int):
    """Auto axes: the sharding-constraint and gather code here is written
    for them (``jax.make_mesh`` defaults to Explicit)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False,
                         split_model: bool = False):
    """Production mesh. ``split_model`` factorizes the 16-way model axis
    into (model1=8, model2=2) so head-structured tensors (GQA kv=8, q=56)
    can shard on a divisor axis instead of being replicated (the optimized
    sharding mode of EXPERIMENTS.md §Perf)."""
    if split_model:
        shape = (2, 16, 8, 2) if multi_pod else (16, 8, 2)
        axes = (("pod",) if multi_pod else ()) + ("data", "model1", "model2")
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))
    # single-pod mesh on a 512-device host: use the first pod's devices
    assert len(devices) >= n, (len(devices), n)
    return jax.sharding.Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_debug_mesh(model: int = 1, data: int = 1):
    """Tiny mesh for CPU smoke runs (1 real device)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))
