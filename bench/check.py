"""The comparison that decides ``correct``: served answers against the
configuration's plain reference, on the same chip.

The number compared is ``max_rel_err``: over the sampled answers, the
largest of max|answer - reference| / max|reference|, each image against
its own reference. The reference runs in blocks of ``BLOCK`` images, one
compiled shape, after the program's state is freed.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 8


class Reservoir:
    """A uniform sample of ``k`` answers out of all that pass by, drawn from
    the seed (algorithm R). Keeps copies, never views of a batch."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k = k
        self.rng = rng
        self.seen = 0
        self.items: List[Tuple[int, np.ndarray, np.ndarray]] = []

    def slot(self) -> Optional[int]:
        """Where the next answer goes in the sample, or None if it stays
        out. The caller stores ``(rid, image, answer copy)`` there."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(None)
            return len(self.items) - 1
        j = int(self.rng.integers(self.seen))
        return j if j < self.k else None


def reference_answers(ref_module, config: dict, weights: Sequence,
                      images: Sequence[np.ndarray], *,
                      precision: str = "highest") -> List[np.ndarray]:
    """The reference's answer for each image (all of one size)."""
    geo = ref_module.geometry(config)
    w_dev = [jnp.asarray(w) for w in weights]
    out: List[np.ndarray] = []
    for b in range(0, len(images), BLOCK):
        part = images[b:b + BLOCK]
        x = np.zeros((BLOCK,) + part[0].shape, np.float32)
        x[:len(part)] = part
        y = np.asarray(ref_module.forward(w_dev, jnp.asarray(x), geo=geo,
                                          precision=precision))
        out.extend(y[:len(part)])
    return out


def max_rel_err(answers: Sequence[np.ndarray],
                refs: Sequence[np.ndarray]) -> float:
    worst = 0.0
    for a, r in zip(answers, refs):
        if a.shape != r.shape or not np.isfinite(a).all():
            return float("inf")
        scale = float(np.abs(r).max())
        err = float(np.abs(a - r).max())
        worst = max(worst, err / scale if scale > 0 else
                    (0.0 if err == 0 else float("inf")))
    return worst


def free_device_memory() -> None:
    """Drop what this process holds on the device before the reference."""
    import gc
    gc.collect()
    jax.clear_caches()
