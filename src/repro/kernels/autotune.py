"""Pack-time per-layer tile autotuner for the sparse conv pipeline.

One tile shape does not fit a whole network: the 3-channel stem wants a
single small GEMM over channel-major patches, the wide mid-layers want
tall row blocks and lazy tap-slab extraction, and the right N-block width
(``bn``) trades schedule length against GEMM width per layer.  This module
scores candidate ``(bm_rows, bn, sub_m, im2col)`` configs for each
:class:`~repro.sparsity.conv.PackedConv` and caches the winner on the
layer, so :func:`repro.vision.model.compile_forward` bakes the tuned work
lists into the whole-net jit.

Scoring is **deterministic and device-free** by default: the step counts
come from the pure-jnp :func:`repro.kernels.worklist_core.schedule_stats`
model (in its static all-live-activations mode — the same counts
``build_worklist`` schedules, which ``tests/test_autotune.py`` pins
exactly), combined with an element-count cost model of the three places
the wall clock actually goes on this pipeline (measured on the vision
bench, see ARCHITECTURE.md):

* **MACs** — ``live_steps * bm * bk * bn``, weight 1;
* **im2col bytes** — the full ``M x K`` patch matrix for the eager
  strategies, but only the *live* union of chunk slabs for ``lazy``
  (patch extraction costs ~10x per element what a GEMM MAC does on
  XLA:CPU, which is why lazy wins wherever dead chunks exist);
* **per-step overhead** — gather/dispatch/flush per scheduled step,
  which is what makes taller ``bm_rows`` (fewer, fatter steps) pay off.

``measure=True`` swaps the model for wall-clock timing of each candidate
through :func:`repro.kernels.sparse_conv.sparse_conv2d_nhwc` on a
calibration input (optional mode — CI never depends on timings).

Bitwise safety: every candidate keeps the layer's pack-time ``bk``, and
per-output-element fp32 accumulation always runs the same ascending
k-chunk order regardless of ``bm_rows``/``bn``/``sub_m``/strategy, so the
tuned network is bit-identical to the default-config network on both
executors (pinned by ``tests/test_autotune.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import bitmask as bm
from repro.kernels.worklist_core import DEFAULT_BM, build_worklist, \
    schedule_stats as conv_schedule_stats
from repro.sparsity.conv import PackedConv, matrixize_filters, \
    pack_conv_filters

# cost-model weights, in units of one GEMM MAC (XLA:CPU vision bench)
COST_MAC = 1.0
COST_EXTRACT = {"patches": 25.0, "slices": 12.0, "taps": 7.0, "lazy": 7.0}
COST_GATHER = 2.0          # per gathered x element, work-list executors
COST_STEP = 20_000.0       # per scheduled step: dispatch + segment/flush
COST_OCC = 0.5             # per occupancy-map entry (sub_m granularity)


@dataclasses.dataclass(frozen=True)
class ConvTileConfig:
    """One runtime tile configuration for a conv layer."""
    bm_rows: int = DEFAULT_BM
    bn: Optional[int] = None          # None: keep the pack-time bn
    sub_m: int = 8
    im2col: str = "auto"

    def key(self) -> Tuple:
        return (self.bm_rows, self.bn, self.sub_m, self.im2col)


@dataclasses.dataclass
class TuneRecord:
    """Autotune outcome cached on ``PackedConv.tuned``."""
    config: ConvTileConfig
    cost: float
    counts: Dict[str, int]            # predicted schedule counts (winner)
    table: List[Tuple[ConvTileConfig, float, Dict[str, int]]]
    m_img: int
    batch: int
    measured: bool = False

    def as_dict(self) -> Dict:
        """JSON-friendly form for bench records."""
        c = self.config
        return {"bm_rows": c.bm_rows, "bn": c.bn, "sub_m": c.sub_m,
                "im2col": c.im2col, "cost": self.cost,
                "measured": self.measured,
                "counts": {k: int(v) for k, v in self.counts.items()},
                "candidates": len(self.table)}


def _occupancy_indices(w_mat: np.ndarray, bk: int, bn: int) -> np.ndarray:
    """Chunk index lists ([nb, max_nz], -1 padded) of a dense [K, N] matrix
    re-cut at (bk, bn) — the occupancy-only half of ``block_sparsify``
    (no value tiles: candidate scoring never touches weights)."""
    K, N = w_mat.shape
    assert K % bk == 0 and N % bn == 0, (K, N, bk, bn)
    kb, nb = K // bk, N // bn
    occupied = (w_mat.reshape(kb, bk, nb, bn) != 0).any(axis=(1, 3)).T
    max_nz = max(int(occupied.sum(1).max(initial=0)), 1)
    indices = np.full((nb, max_nz), -1, np.int32)
    for n in range(nb):
        ks = np.nonzero(occupied[n])[0]
        indices[n, : ks.shape[0]] = ks
    return indices


def candidate_configs(conv: PackedConv, m_img: int, *,
                      batch: int = 1) -> List[ConvTileConfig]:
    """Deterministic candidate grid for one layer.

    ``bm_rows``: the default grid block plus the whole-image block (one
    row block per image, 64-aligned — fewest steps). ``bn``: the
    pack-time width plus any chunk-compatible alternatives. ``im2col``:
    the strategies legal for the layer's layout (``lazy`` only where each
    K-chunk lies inside one tap, and it only ever helps when dead chunks
    exist, but it is scored, not assumed).
    """
    m_img = int(m_img)
    cout = conv.cout
    bms = {DEFAULT_BM}
    whole = -(-m_img // 64) * 64
    if whole <= 4096:
        bms.add(whole)
    bns = {conv.packed.bn}
    for cand in (64, bm.CHUNK):
        if cout % cand == 0:
            bns.add(cand)
    if conv.layout != "tap":
        strategies = ("patches", "slices")
    elif conv.cin % conv.packed.bk:
        strategies = ("taps",)
    else:
        strategies = ("taps", "lazy")
    return [ConvTileConfig(bm_rows=bmr, bn=bnn, sub_m=8, im2col=s)
            for bmr in sorted(bms) for bnn in sorted(bns)
            for s in strategies]


def score_config(cfg: ConvTileConfig, conv: PackedConv, m_img: int, *,
                 batch: int = 1,
                 occ_blk: Optional[np.ndarray] = None
                 ) -> Tuple[float, Dict[str, int]]:
    """Deterministic cost of one candidate: schedule counts from the
    pure-jnp :func:`conv_schedule_stats` model (static mode unless a
    calibration occupancy is given) + the element-count cost terms.
    Returns ``(cost, counts)``; lower is better.
    """
    bk = conv.packed.bk
    bn = cfg.bn if cfg.bn is not None else conv.packed.bn
    k_total = conv.packed.shape[0]
    m_pad = m_img + (-m_img) % cfg.bm_rows
    mb = batch * m_pad // cfg.bm_rows
    if bn == conv.packed.bn:
        indices = conv.packed.host_indices()
    else:
        w_mat = matrixize_filters(conv.w_dense, layout=conv.layout,
                                  bk=bk, bn=bn)
        indices = _occupancy_indices(w_mat, bk, bn)
    if occ_blk is not None:
        occ = np.tile(np.asarray(occ_blk, bool), (batch, 1))[:mb]
        stats = conv_schedule_stats(None, jnp.asarray(indices), bk=bk,
                                    bm_rows=cfg.bm_rows, occ=occ)
    else:
        stats = conv_schedule_stats(None, jnp.asarray(indices), bk=bk,
                                    bm_rows=cfg.bm_rows, mb=mb)
    counts = {k: int(stats[k]) for k in
              ("live_chunk_steps", "dead_pairs", "scheduled_steps",
               "dense_grid_steps")}
    live = counts["live_chunk_steps"]
    nb = indices.shape[0]
    kb = k_total // bk
    M = batch * m_pad
    mac = COST_MAC * live * cfg.bm_rows * bk * bn
    if cfg.im2col == "lazy":
        union = np.unique(indices[indices >= 0])
        extract = COST_EXTRACT["lazy"] * M * bk * union.size
    else:
        strat = cfg.im2col
        if strat == "auto":
            strat = "slices"
        extract = COST_EXTRACT.get(strat, 12.0) * M * k_total
    gather = COST_GATHER * live * cfg.bm_rows * bk
    step = COST_STEP * counts["scheduled_steps"]
    occ_cost = COST_OCC * (M // cfg.sub_m) * kb
    return mac + extract + gather + step + occ_cost, counts


def _measure_config(cfg: ConvTileConfig, conv: PackedConv, x, stride,
                    padding, reps: int = 5) -> float:
    """Wall-clock a candidate through the real kernel path (optional
    measured mode — never used by CI gates)."""
    import jax
    from repro.kernels.sparse_conv import sparse_conv2d_nhwc
    packed = conv.packed
    if cfg.bn is not None and cfg.bn != packed.bn:
        packed = pack_conv_filters(conv.w_dense, layout=conv.layout,
                                   bk=packed.bk, bn=cfg.bn)
    fn = jax.jit(lambda v: sparse_conv2d_nhwc(
        v, packed, conv.kh, conv.kw, conv.cout, stride=stride,
        padding=padding, sub_m=cfg.sub_m, bm_rows=cfg.bm_rows,
        im2col=cfg.im2col, layout=conv.layout)[0])
    fn(x).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        fn(x).block_until_ready()
    return (time.time() - t0) / reps


def autotune_conv(conv: PackedConv, m_img: int, *, batch: int = 1,
                  candidates: Optional[Sequence[ConvTileConfig]] = None,
                  occ_blk: Optional[np.ndarray] = None,
                  measure: bool = False, x=None, stride=1,
                  padding="SAME", repack: bool = True) -> TuneRecord:
    """Tune one layer; caches the result on ``conv.tuned``.

    Deterministic: candidates are scored in a fixed order with the
    device-free cost model and ties break toward the earlier candidate,
    so re-tuning an identical layer reproduces the identical
    :class:`TuneRecord` (pinned by ``tests/test_autotune.py``).  When the
    winner's ``bn`` differs from the pack-time width and ``repack`` is
    set, the layer is re-packed at the tuned ``bn`` (same ``bk``, so
    per-element accumulation order — and therefore bits — is unchanged)
    and the stale work-list cache is dropped.
    """
    m_img = int(m_img)
    cands = list(candidates) if candidates is not None else \
        candidate_configs(conv, m_img, batch=batch)
    if not cands:
        raise ValueError("no candidate configs")
    table: List[Tuple[ConvTileConfig, float, Dict[str, int]]] = []
    for cfg in cands:
        cost, counts = score_config(cfg, conv, m_img, batch=batch,
                                    occ_blk=occ_blk)
        if measure:
            if x is None:
                raise ValueError("measure=True needs a calibration input x")
            cost = _measure_config(cfg, conv, x, stride, padding)
        table.append((cfg, cost, counts))
    best = min(range(len(table)), key=lambda i: table[i][1])
    cfg, cost, counts = table[best]
    rec = TuneRecord(cfg, float(cost), counts, table, m_img, batch,
                     measured=measure)
    if repack and cfg.bn is not None and cfg.bn != conv.packed.bn:
        conv.packed = pack_conv_filters(conv.w_dense, layout=conv.layout,
                                        bk=conv.packed.bk, bn=cfg.bn)
        conv.wl_cache.clear()
    conv.tuned = rec
    return rec


def autotune_model(model, image_size: Optional[int] = None, *,
                   batch: int = 1, measure: bool = False,
                   x=None) -> Dict[int, TuneRecord]:
    """Walk a :class:`~repro.vision.model.VisionModel`'s layer geometry and
    tune every conv; clears the model's compiled-forward cache so the next
    ``compile_forward`` bakes the tuned schedules."""
    from repro.kernels.sparse_conv import conv_out_size
    size = image_size if image_size is not None else model.input_size
    H = W = size
    records: Dict[int, TuneRecord] = {}
    xi = x
    for i, layer in enumerate(model.layers):
        c = layer.conv
        oh, ow = conv_out_size(H, W, c.kh, c.kw, layer.stride, layer.padding)
        records[i] = autotune_conv(
            c, oh * ow, batch=batch, measure=measure, x=xi,
            stride=layer.stride, padding=layer.padding)
        H, W = oh, ow
        if layer.pool_after is not None and min(H, W) >= layer.pool_after[0]:
            win, st = layer.pool_after
            H = (H - win) // st + 1
            W = (W - win) // st + 1
        if measure and xi is not None:
            import jax
            from repro.kernels.sparse_conv import sparse_conv2d_nhwc
            from repro.vision.model import max_pool as _mp
            xi, _ = sparse_conv2d_nhwc(
                xi, c.packed, c.kh, c.kw, c.cout, stride=layer.stride,
                padding=layer.padding, layout=c.layout,
                wl_cache=c.wl_cache)
            if layer.pool_after is not None:
                xi = _mp(xi, *layer.pool_after)
    model._fwd_cache.clear()
    return records
