"""Seeded conv weights, made on the device in one jitted call.

Each layer is He-normal (std = sqrt(2 / fan_in)) and magnitude-pruned per
output filter: the ``keep`` largest |w| of each filter survive, with
``keep = max(round(fan_in * filter_density), 1)``. So the number of
nonzero weights is fixed by the configuration, whatever the seed; the
seed only moves which weights they are and their values.

The program (``build_sparse_chain``) is handed these pruned filters with
density 1.0: it balances, folds and packs them, and prunes nothing more.
The plain reference reads the same filters. Nothing here imports the
program.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def filter_shapes(config: dict) -> List[Tuple[int, int, int, int]]:
    """[kh, kw, Cin, Cout] of each conv layer in the configuration."""
    return [(l["kernel"], l["kernel"], l["in_channels"], l["out_channels"])
            for l in config["layers"]]


def kept_per_filter(shape: Sequence[int], density: float) -> int:
    """Nonzero weights in each output filter after pruning."""
    kh, kw, cin, _ = shape
    return max(int(round(kh * kw * cin * density)), 1)


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words that carry a seed of up to 64 bits into jit."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


@functools.partial(jax.jit, static_argnames=("shapes", "keeps"))
def _make(words, *, shapes, keeps):
    key = jax.random.fold_in(jax.random.key(words[0]), words[1])
    out = []
    for i, (shape, keep) in enumerate(zip(shapes, keeps)):
        kh, kw, cin, cout = shape
        fan_in = kh * kw * cin
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        w = w * np.float32(np.sqrt(2.0 / fan_in))
        mag = jnp.abs(w).reshape(fan_in, cout)
        thresh = jnp.sort(mag, axis=0)[fan_in - keep]
        out.append(jnp.where(mag >= thresh[None, :], w.reshape(fan_in, cout),
                             0.0).reshape(shape))
    return tuple(out)


def make_weights(config: dict, seed: int) -> List[np.ndarray]:
    """Pruned float32 filters for every layer, on the host."""
    shapes = tuple(filter_shapes(config))
    keeps = tuple(kept_per_filter(s, config["filter_density"])
                  for s in shapes)
    ws = _make(jnp.asarray(seed_words(seed)), shapes=shapes, keeps=keeps)
    return [np.asarray(w) for w in ws]
