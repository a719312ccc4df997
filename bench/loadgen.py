"""The one traffic generator: images and callers from a traffic file and
``--seed``.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

* ``loop``: ``"closed"``, the loop this generator drives: ``clients``
  callers, each sends its next image when its last answer is back;
* ``slots``: the server's batch width;
* ``image_pool``: distinct images made per run, at the configuration's
  input size, and drawn from by a seeded choice per request.
"""
from __future__ import annotations

import numpy as np


def blob_images(rng: np.random.Generator, n: int, size: int,
                live_frac: float) -> np.ndarray:
    """Synthetic feature-map-sparse inputs: non-negative blobs on a zero
    background, ~``live_frac`` of the pixels live (the paper's ReLU
    feature-map sparsity, spatially clustered)."""
    if not 0.0 <= live_frac <= 1.0:
        raise ValueError(f"live_frac must be in [0, 1], got {live_frac}")
    imgs = np.zeros((n, size, size, 3), np.float32)
    for i in range(n):
        area = 0.0
        # bounded: each blob adds coverage in expectation; near-1 targets
        # stop at the cap instead of chasing the last uncovered pixels
        for _ in range(64 * max(size, 1)):
            if area >= live_frac:
                break
            h = rng.integers(1, max(size // 2, 2))
            w = rng.integers(1, max(size // 2, 2))
            r, c = rng.integers(0, size - h + 1), rng.integers(0, size - w + 1)
            imgs[i, r:r + h, c:c + w] = np.abs(
                rng.normal(size=(h, w, 3))).astype(np.float32)
            area = (imgs[i] != 0).mean()
    return imgs


class ImagePool:
    """``image_pool`` distinct images at the configuration's input size,
    made from the seed; each request gets one by a seeded draw."""

    def __init__(self, rng: np.random.Generator, traffic: dict,
                 config: dict):
        self.images = blob_images(rng, int(traffic["image_pool"]),
                                  int(config["input_size"]),
                                  config["map_density"])
        self._rng = rng

    def image(self) -> np.ndarray:
        return self.images[int(self._rng.integers(len(self.images)))]


def clients(traffic: dict) -> int:
    """The closed loop's number of callers."""
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}: this generator "
                         f"drives a closed loop")
    return int(traffic["clients"])
