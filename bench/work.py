"""The work a conv chain requires, from its shapes: the yardstick for
``step_mfu`` and ``walker_roofline``.

Counts are those of the pruned network itself, whatever implements it:

* MACs of a layer = output positions x weights (dense), or output
  positions x nonzero weights (required);
* bytes of a layer, float32 = its input map + its nonzero weight values
  + its output map. The weights are read once per call, the maps once per
  image.

Nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

F32 = 4


def _out_size(h: int, k: int, stride: int, padding: str) -> int:
    if padding == "SAME":
        return -(-h // stride)
    if padding == "VALID":
        return (h - k) // stride + 1
    raise ValueError(f"unknown padding {padding!r}")


@dataclasses.dataclass(frozen=True)
class LayerWork:
    in_hw: int
    out_hw: int
    cin: int
    cout: int
    kernel: int
    nonzero: int                  # nonzero filter weights of the layer

    @property
    def positions(self) -> int:
        return self.out_hw * self.out_hw

    @property
    def dense_macs(self) -> int:
        return self.positions * self.kernel * self.kernel * self.cin \
            * self.cout

    @property
    def macs(self) -> int:
        return self.positions * self.nonzero

    def flops(self, images: int) -> int:
        return 2 * self.macs * images

    def bytes(self, images: int) -> int:
        maps = self.in_hw * self.in_hw * self.cin + self.positions * self.cout
        return F32 * (images * maps + self.nonzero)


def chain_work(config: dict, size: int,
               nonzero: Sequence[int]) -> List[LayerWork]:
    """Per-layer work of the chain at a square input of ``size`` pixels;
    ``nonzero[i]`` is the count of nonzero weights of layer ``i``."""
    out: List[LayerWork] = []
    h = size
    for layer, nz in zip(config["layers"], nonzero):
        oh = _out_size(h, layer["kernel"], layer["stride"], layer["padding"])
        out.append(LayerWork(h, oh, layer["in_channels"],
                             layer["out_channels"], layer["kernel"], int(nz)))
        h = oh
        if layer["pool_after"]:
            win, s = layer["pool_after"]
            h = (h - win) // s + 1
    return out


def nonzero_counts(weights: Sequence[np.ndarray]) -> List[int]:
    return [int(np.count_nonzero(w)) for w in weights]


def flops_per_image(layers: Sequence[LayerWork]) -> int:
    return sum(l.flops(1) for l in layers)


def roofline_seconds(layers: Sequence[LayerWork], images: int,
                     peak_flops: float, peak_bytes_s: float) -> float:
    """Least time the chip could take for one call over ``images`` images:
    per layer the larger of its FLOPs over peak and its bytes over HBM
    bandwidth, summed over the layers."""
    return sum(max(l.flops(images) / peak_flops,
                   l.bytes(images) / peak_bytes_s) for l in layers)
