"""The work a configuration's layer table requires, from its shapes: the
yardstick for ``step_mfu`` and ``walker_roofline``.

Counts are those of the pruned network itself, whatever implements it:

* MACs of a layer = output positions x weights (dense), or output
  positions x nonzero weights (required); FLOPs are 2 x MACs, and a
  residual add counts no FLOPs;
* bytes of a layer, float32 = its input map + its nonzero weight values
  + its output map, and for an entry with ``add`` the added map once more
  (the add is required work, whatever implements it). The weights are
  read once per call, the maps once per image.

The table may be a graph (``spec.layer_graph``): each entry reads the map
of the entry it names, at that entry's size after its pool. Nothing here
imports the program.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

import spec

F32 = 4


def _out_size(h: int, k: int, stride: int, padding: spec.Pad) -> int:
    if padding == "SAME":
        return -(-h // stride)
    if padding == "VALID":
        return (h - k) // stride + 1
    (lo, hi), _ = padding
    return (h + lo + hi - k) // stride + 1


@dataclasses.dataclass(frozen=True)
class LayerWork:
    in_hw: int
    out_hw: int
    cin: int
    cout: int
    kernel: int
    nonzero: int                  # nonzero filter weights of the layer
    add: bool = False             # a map is added to the conv's output

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
        maps = self.in_hw * self.in_hw * self.cin \
            + self.positions * self.cout * (2 if self.add else 1)
        return F32 * (images * maps + self.nonzero)


def table_work(config: dict, size: int,
               nonzero: Sequence[int]) -> List[LayerWork]:
    """Per-entry work of the layer table at a square input of ``size``
    pixels; ``nonzero[i]`` is the count of nonzero weights of entry ``i``.
    Raises ValueError where an added map's size differs from the conv's."""
    out: List[LayerWork] = []
    sizes: List[int] = []         # each entry's output size, after its pool
    for layer, nz in zip(spec.layer_graph(config), nonzero):
        h = size if layer.input == spec.IMAGE else sizes[layer.input]
        oh = _out_size(h, layer.kernel, layer.stride, layer.padding)
        if layer.add is not None and sizes[layer.add] != oh:
            raise ValueError(f"layer table {layer.label}: add reads a "
                             f"{sizes[layer.add]} px map onto {oh} px")
        out.append(LayerWork(h, oh, layer.cin, layer.cout, layer.kernel,
                             int(nz), layer.add is not None))
        if layer.pool:
            win, s, pad = layer.pool
            oh = _out_size(oh, win, s, pad)
        sizes.append(oh)
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
