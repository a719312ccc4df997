"""Plain reference of a configuration's layer table: per entry conv, the
residual add, ReLU, max-pool, entry by entry.

It reads the layer table of the configuration file, a chain or a graph
(``spec.layer_graph``), and the pruned filters of ``bench/weights.py``,
and imports nothing of the program. Each entry reads the image or the
output of the entry it names. On a chain it runs conv, ReLU and pool,
layer by layer, as a chain-only reference would.
``precision="highest"`` is the float32 reference the served answers are
held to. ``precision="high3"`` is the control: the same network in three
bf16 passes per product (hi*hi + hi*lo + lo*hi, each operand split into a
bf16 high part and a bf16 remainder), which is what a float32 matmul at
``Precision.HIGH`` does on a TPU. It is spelt out here so that it reads
the same on every backend; the split uses ``reduce_precision``, which the
compiler keeps (a float32 -> bfloat16 -> float32 round trip it may drop).
``precision="high"`` asks the backend for ``Precision.HIGH`` itself (on a
CPU that is float32 again).
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

import spec

HIGHEST = jax.lax.Precision.HIGHEST


def _conv(x, w, stride, padding, precision=HIGHEST):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _conv_high(x, w, stride, padding):
    return _conv(x, w, stride, padding, jax.lax.Precision.HIGH)


def _bf16(a):
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _conv_high3(x, w, stride, padding):
    xh, xl = _split(x)
    wh, wl = _split(w)
    return (_conv(xh, wh, stride, padding) + _conv(xh, wl, stride, padding)
            + _conv(xl, wh, stride, padding))


def geometry(config: dict) -> Tuple[tuple, ...]:
    """Hashable (stride, padding, pool, input, add, relu) per entry, for
    the jit below; ``input`` and ``add`` are entry indices (``input`` -1
    for the image), ``pool`` is (window, stride, padding) or None."""
    return tuple((l.stride, l.padding, l.pool, l.input, l.add, l.relu)
                 for l in spec.layer_graph(config))


def _pool(x, pool):
    win, s, pad = pool
    if pad != "VALID":
        pad = ((0, 0),) + pad + ((0, 0),)
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, win, win, 1),
                                 (1, s, s, 1), pad)


@functools.partial(jax.jit, static_argnames=("geo", "precision"))
def forward(weights: Sequence[jnp.ndarray], x: jnp.ndarray, *, geo,
            precision: str = "highest") -> jnp.ndarray:
    """[B, H, W, Cin] images -> the last entry's output."""
    conv = {"highest": _conv, "high3": _conv_high3,
            "high": _conv_high}[precision]
    outs = []
    for w, (stride, padding, pool, src, add, relu) in zip(weights, geo):
        y = conv(x if src == spec.IMAGE else outs[src], w, stride, padding)
        if add is not None:
            y = y + outs[add]
        if relu:
            y = jnp.maximum(y, 0.0)
        if pool is not None:
            y = _pool(y, pool)
        outs.append(y)
    return outs[-1]
