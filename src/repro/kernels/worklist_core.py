"""Unified work-list sparse GEMM core (BARISTA §3.2 telescoped scheduling).

This module is the single sparse runtime under the repo's three frontends:

* ``kernels.sparse_conv``   — the vision path (im2col + §3.3 coloring),
* ``kernels.bitmask_spmm`` / ``kernels.fused_ffn`` — the LM FFN path
  (plain and fused in-proj/activation/gate matmuls),
* ``serve`` / ``vision`` engines — which read one unified
  schedule-counters record instead of three per-frontend formats.

The paper's central scheduling idea is that sparsity should be exploited
by *not scheduling* dead work, not by predicating it away in-lane. The
core owns the four pieces every frontend shares:

1. :func:`build_worklist` + :class:`WorkList` — compact a packed weight
   chunk table (optionally ∩ the activation-chunk occupancy, and
   optionally unioned with a second *gate* weight stream for the gated
   FFN) into the ragged-padded per-pair schedule and its flat pair-major
   serialization.
2. The **Pallas walker** (:func:`worklist_spmm`, ``executor="pallas"``) —
   grid = the flat work list, one dense MXU tile MAC per scheduled step,
   dead (n, m) pairs degenerating to flush-only steps. Parameterized by
   stream count (1, or 2 for gated FFN), output-buffer color count
   (2 for the conv §3.3 image-parity coloring, 1 otherwise), a fused
   activation epilogue (``act``), and in-kernel occupancy emission.
3. The **XLA executor** (``executor="xla"``) — gather exactly the
   scheduled tile pairs, one batched GEMM, segment-sum per (n, m) pair
   in schedule order: the same fp32 accumulation order as the walker, so
   outputs are bit-identical (the property tests pin this per frontend).
4. :func:`schedule_stats` — the pure-jnp cost model predicting exactly
   the step counts :func:`build_worklist` schedules (pinned by tests),
   usable under jit (serving probes) and by the autotuner's device-free
   candidate scoring.

It also owns the call-time backend resolvers (:func:`on_tpu`,
:func:`resolve_interpret`, :func:`resolve_executor`) — previously
duplicated between ``kernels.ops`` and ``kernels.sparse_conv`` — and the
:func:`schedule_counters` record schema both engines report.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
LANE = 128

# committed cluster-balance bound: per-device scheduled-step counts of a
# mesh-sharded work list stay within this fraction of the mean (the §4
# round-robin balance target lifted to cluster granularity). The packer's
# mesh-aware balance step targets it, WL-SHARD-BAL audits it, and the
# dist-vision regression gate holds the committed bench to it.
SHARD_BALANCE_TOL = 0.10

GATED_ACTS = ("swiglu", "geglu")
ACTS = ("relu", "relu2", "gelu") + GATED_ACTS


# ---------------------------------------------------------------------------
# call-time backend resolution (single source — everything imports these)
# ---------------------------------------------------------------------------
def on_tpu() -> bool:
    """Backend check at call time (NOT frozen at import — the backend may
    be initialized after this module imports, e.g. by dist mesh setup)."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas interpret default: compiled on TPU, interpreter elsewhere.
    Resolved from ``jax.default_backend()`` *now*, never from an
    import-time snapshot."""
    return (not on_tpu()) if interpret is None else interpret


def resolve_executor(executor: Optional[str]) -> str:
    """Work-list walker for this backend: pallas on TPU, xla on CPU (its
    scatter-add runs in schedule order — bit-identical to the grid), the
    pallas interpreter anywhere else (GPU scatter-adds are atomic and
    would only promise rtol agreement, not bits)."""
    if executor is not None:
        return executor
    if on_tpu():
        return "pallas"
    return "xla" if jax.default_backend() == "cpu" else "pallas"


# ---------------------------------------------------------------------------
# activation epilogue (shared by the fused FFN kernel and both walkers)
# ---------------------------------------------------------------------------
def activate(h: jnp.ndarray, g: Optional[jnp.ndarray],
             act: Optional[str]) -> jnp.ndarray:
    """fp32 activation at the accumulator flush (same table as
    ``models.layers._activate``, restricted to the sparse-eligible acts;
    ``None`` is the identity epilogue)."""
    if act is None:
        return h
    if act == "relu":
        return jnp.maximum(h, 0.0)
    if act == "relu2":
        r = jnp.maximum(h, 0.0)
        return r * r
    if act == "gelu":
        return jax.nn.gelu(h)
    if act == "swiglu":
        return jax.nn.silu(g) * h
    if act == "geglu":
        return jax.nn.gelu(g) * h
    raise ValueError(act)


def activation_occupancy(x: jnp.ndarray, sub_m: int, bk: int) -> jnp.ndarray:
    """int32 [M // sub_m, K // bk] tile-occupancy of ``x`` at ``sub_m``-row
    granularity (the activation-side skip predicate every frontend uses)."""
    M, K = x.shape
    return (x.reshape(M // sub_m, sub_m, K // bk, bk) != 0).any(
        axis=(1, 3)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Telescoped work-list compaction (BARISTA §3.2 applied to the grid)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class CombinedSchedule:
    """Cross-request telescoped fetch plan for one batched schedule.

    §3.2 request combining lifted *across the images of a batch*: the
    flat work list schedules one weight-chunk read per live step, but
    images sharing a batch walk the same pack-time chunk lists, so a
    filter chunk ``(n_block, k-chunk)`` requested by several images needs
    only **one** fetch per batch. This plan is *derived from* the flat
    schedule — execution order (and hence the fp32 accumulation-order
    bitwise contract) is untouched; only the fetch stream is deduped.

    ``fetch_*`` list the deduped fetches in schedule order:
    ``fetch_at[i]`` is the flat step at which chunk
    ``(fetch_stream[i], fetch_n[i], fetch_k[i])`` is first requested
    (stream 1 is the gated FFN's second weight stream). ``requests`` is
    what the un-combined schedule would issue (one read per live step
    and stream); ``per_image_fetches`` is the per-image-dedup baseline
    (each image fetches its own distinct live chunks — what per-request
    sequential serving does); ``num_fetches`` is the batch-wide dedup.
    """

    fetch_stream: np.ndarray          # [F] int32 (0 = k, 1 = k2/gate)
    fetch_n: np.ndarray               # [F] int32 n_block
    fetch_k: np.ndarray               # [F] int32 weight k-chunk id
    fetch_at: np.ndarray              # [F] int64 issuing flat step
    mb_per_img: int
    images: int
    requests: int
    per_image_fetches: int

    @property
    def num_fetches(self) -> int:
        return int(self.fetch_n.shape[0])

    @property
    def cross_request_combine_factor(self) -> float:
        """Fetches saved vs per-request sequential execution (≈ the batch
        width when the batch shares one static schedule; 1.0 at batch 1)."""
        return self.per_image_fetches / max(self.num_fetches, 1)

    @property
    def combine_factor(self) -> float:
        """Total schedule reads per actual fetch (intra-image reuse x
        cross-request dedup)."""
        return self.requests / max(self.num_fetches, 1)


def _build_combined(wl: "WorkList", mpi: int) -> CombinedSchedule:
    """Dedup the flat schedule's per-step chunk reads batch-wide (one
    fetch per distinct (stream, n_block, chunk)) and count the per-image
    baseline. Pure host numpy over the already-built flat arrays."""
    if wl.mb % mpi:
        raise ValueError(f"mb_per_img={mpi} does not divide mb={wl.mb}")
    images = wl.mb // mpi
    streams: Tuple[Tuple[int, np.ndarray], ...] = ((0, wl.k),)
    if wl.k2 is not None:
        streams = streams + ((1, wl.k2),)
    f_stream, f_n, f_k, f_at = [], [], [], []
    requests = 0
    per_image = 0
    for sid, ks in streams:
        live = np.nonzero(ks >= 0)[0]
        if live.size == 0:
            continue
        n64 = wl.n[live].astype(np.int64)
        k64 = ks[live].astype(np.int64)
        kmax = int(k64.max()) + 1
        key = n64 * kmax + k64
        # np.unique's return_index is the *first* occurrence — `live` is
        # in flat-schedule order, so fetch_at is the earliest request
        _, first_idx = np.unique(key, return_index=True)
        f_stream.append(np.full(first_idx.size, sid, np.int32))
        f_n.append(wl.n[live][first_idx])
        f_k.append(ks[live][first_idx])
        f_at.append(live[first_idx].astype(np.int64))
        requests += int(live.size)
        img = (wl.m[live] // mpi).astype(np.int64)
        per_image += int(np.unique(img * (wl.nb * kmax) + key).size)
    if f_n:
        stream = np.concatenate(f_stream)
        n_arr = np.concatenate(f_n)
        k_arr = np.concatenate(f_k)
        at = np.concatenate(f_at)
        order = np.argsort(at, kind="stable")   # schedule-ordered plan
        stream, n_arr, k_arr, at = (stream[order], n_arr[order],
                                    k_arr[order], at[order])
    else:
        stream = n_arr = k_arr = np.zeros((0,), np.int32)
        at = np.zeros((0,), np.int64)
    return CombinedSchedule(stream, n_arr, k_arr, at, mpi, images,
                            requests, per_image)


@dataclasses.dataclass
class WorkList:
    """Compacted schedule for a chunk-block-sparse matmul grid.

    The dense grid runs ``nb * mb * max_nz`` steps and *predicates* dead
    work away inside the lane. This schedule instead enumerates, per
    ``(n_block, m_block)`` pair, the intersection of the stored filter
    chunk list with the activation-chunk occupancy, so dead ``k`` steps
    are never scheduled at all. Two equivalent forms are kept:

    * ``ragged_idx [nb, mb, max_live]`` + ``steps_per_pair [nb, mb]`` —
      the ragged-padded per-pair slot lists (slot = position in the packed
      ``vals``; -1 padded),
    * flat arrays ``n/m/k/j/first/last [num_steps]`` — the same entries
      serialized pair-major (n outer, m inner, live slots in j order),
      which is what drives the Pallas grid / XLA executor. A pair with no
      live work degenerates to a single flush-only step (``k == j == -1``)
      so its output block is still written (zeros).

    For a two-stream (gated FFN) schedule, ``k2`` carries the second
    weight stream's chunk id per step (-1 where that stream is dead at
    the slot); the flat slots are the *union* of the two streams' live
    sets, so each stream MACs in its own ascending-``j`` order — the same
    per-element fp32 accumulation order as the predicated kernel.

    ``mac_steps`` counts steps with any live MAC; ``num_steps`` adds the
    flush-only steps. The dense grid would have scheduled
    ``dense_grid_steps`` (at this schedule's own row-block granularity).
    """

    n: np.ndarray
    m: np.ndarray
    k: np.ndarray
    j: np.ndarray
    first: np.ndarray
    last: np.ndarray
    ragged_idx: np.ndarray
    steps_per_pair: np.ndarray
    nb: int
    mb: int
    max_nz: int
    k2: Optional[np.ndarray] = None
    # images sharing this batched schedule (mb == images * mb_per_img);
    # None = unknown (single-image / FFN schedules). Set by the conv
    # frontend so serving layers can derive cross-request fetch plans.
    mb_per_img: Optional[int] = None
    # cluster assignment of the n-blocks ([nb] int32 device ids, from the
    # packer's mesh-aware balance step); None = unsharded schedule. The
    # per-device step counters and the WL-SHARD-BAL audit read this.
    shard_of: Optional[np.ndarray] = None
    _combined: Dict[int, CombinedSchedule] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def num_steps(self) -> int:
        return int(self.n.shape[0])

    @property
    def num_pairs(self) -> int:
        return self.nb * self.mb

    @property
    def live_mask(self) -> np.ndarray:
        live = self.k >= 0
        if self.k2 is not None:
            live = live | (self.k2 >= 0)
        return live

    @property
    def mac_steps(self) -> int:
        return int(self.live_mask.sum())

    @property
    def flush_only_steps(self) -> int:
        return self.num_steps - self.mac_steps

    @property
    def dense_grid_steps(self) -> int:
        return self.nb * self.mb * self.max_nz

    def prefetch_args(self):
        """The flat schedule as device arrays in kernel argument order."""
        arrs = (self.n, self.m, self.k, self.j, self.first, self.last)
        if self.k2 is not None:
            arrs = arrs + (self.k2,)
        return tuple(jnp.asarray(a) for a in arrs)

    def combined(self, mb_per_img: Optional[int] = None) -> CombinedSchedule:
        """The cross-request telescoped fetch plan for this schedule
        (cached per image granularity). ``mb_per_img`` overrides the
        build-time value; with neither set the whole batch counts as one
        image (cross factor 1.0 — nothing to combine across)."""
        mpi = mb_per_img if mb_per_img is not None else self.mb_per_img
        mpi = self.mb if mpi is None else mpi
        cs = self._combined.get(mpi)
        if cs is None:
            cs = _build_combined(self, mpi)
            self._combined[mpi] = cs
        return cs


# imported under this name by the conv frontend since PR 5
ConvWorkList = WorkList


def _live_map(indices: np.ndarray, mb: int,
              occ_blk: Optional[np.ndarray]) -> np.ndarray:
    """live[n, m, j] = stored chunk j of n-block ∧ activation block
    (m, chunk) occupied (all blocks count as occupied when ``occ_blk`` is
    None — the static pack-time schedule)."""
    nb, max_nz = indices.shape
    valid = indices >= 0
    if occ_blk is None:
        return np.broadcast_to(valid[:, None, :], (nb, mb, max_nz))
    occ_blk = np.asarray(occ_blk, bool)
    assert occ_blk.shape[0] == mb, (occ_blk.shape, mb)
    safe = np.where(valid, indices, 0)
    return valid[:, None, :] & occ_blk[:, safe].transpose(1, 0, 2)


def build_worklist(indices: np.ndarray, mb: int, *,
                   occ_blk: Optional[np.ndarray] = None,
                   gate_indices: Optional[np.ndarray] = None,
                   mb_per_img: Optional[int] = None,
                   shard_of: Optional[np.ndarray] = None) -> WorkList:
    """Compact a [nb, max_nz] chunk index table into a :class:`WorkList`.

    ``indices`` is the packed weight layout's per-n-block k-chunk list (-1
    padded) — host numpy, known at pack time. ``occ_blk`` (optional bool
    [mb, kb]) is the activation occupancy at (row-block x chunk)
    granularity; when given, the per-pair lists are the *intersection*
    (two-sided compaction — data-dependent, so eager callers only).
    ``gate_indices`` (optional, same shape) adds a second weight stream
    sharing the slot axis (the gated FFN's aligned in/gate chunk lists):
    the schedule is the *union* of the two streams' live sets and the
    flat ``k``/``k2`` arrays carry each stream's chunk per step (-1 where
    that stream is dead at the slot). ``mb_per_img`` records how many
    row blocks belong to one image of the batch (the conv frontend's
    ``m_pad // bm_rows``) so :meth:`WorkList.combined` can derive the
    cross-request telescoped fetch plan. ``shard_of`` (optional int32
    [nb]) records the packer's cluster assignment of each n-block so the
    per-device step counters (:func:`per_shard_steps`) and the
    WL-SHARD-BAL balance audit can attribute scheduled steps to devices.
    """
    indices = np.asarray(indices)
    if mb_per_img is not None and mb % mb_per_img:
        raise ValueError(f"mb_per_img={mb_per_img} does not divide mb={mb}")
    nb, max_nz = indices.shape
    if shard_of is not None:
        shard_of = np.asarray(shard_of, np.int32)
        if shard_of.shape != (nb,):
            raise ValueError(f"shard_of shape {shard_of.shape} != ({nb},)")
    live1 = _live_map(indices, mb, occ_blk)
    if gate_indices is None:
        live = live1
    else:
        gate_indices = np.asarray(gate_indices)
        assert gate_indices.shape == indices.shape, \
            (gate_indices.shape, indices.shape)
        live2 = _live_map(gate_indices, mb, occ_blk)
        live = live1 | live2
    steps = live.sum(-1).astype(np.int64)                    # [nb, mb]
    max_live = max(int(steps.max(initial=0)), 1)
    # live slots first (stable keeps ascending j order), then -1 padding
    order = np.argsort(~live, axis=-1, kind="stable")
    ragged = np.where(np.arange(max_nz)[None, None, :] < steps[..., None],
                      order, -1)[..., :max_live].astype(np.int32)
    # flatten pair-major; dead pairs contribute one flush-only step
    counts = np.maximum(steps, 1).reshape(-1)                # [nb*mb]
    total = int(counts.sum())
    pair = np.repeat(np.arange(nb * mb), counts)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(total) - starts[pair]
    n_arr = (pair // mb).astype(np.int32)
    m_arr = (pair % mb).astype(np.int32)
    j_arr = ragged.reshape(nb * mb, max_live)[
        pair, np.minimum(pos, max_live - 1)]
    j_clip = np.maximum(j_arr, 0)

    def stream_k(idx, lv):
        hit = (j_arr >= 0) & lv[n_arr, m_arr, j_clip]
        return np.where(hit, idx[n_arr, j_clip], -1).astype(np.int32)

    k_arr = stream_k(indices, live1)
    k2_arr = stream_k(gate_indices, live2) if gate_indices is not None \
        else None
    first = (pos == 0).astype(np.int32)
    last = (pos == counts[pair] - 1).astype(np.int32)
    return WorkList(n_arr, m_arr, k_arr, j_arr.astype(np.int32), first,
                    last, ragged, steps.astype(np.int32), nb, mb, max_nz,
                    k2=k2_arr, mb_per_img=mb_per_img, shard_of=shard_of)


# ---------------------------------------------------------------------------
# per-shard schedule accounting (the §4 round-robin balance, observable)
# ---------------------------------------------------------------------------
def per_shard_steps(wl: WorkList,
                    num_shards: Optional[int] = None) -> np.ndarray:
    """Scheduled steps per device of a mesh-sharded work list.

    Device ``d`` walks exactly the flat entries of its assigned n-blocks —
    live MACs plus one flush-only step per dead (n, m) pair — so its step
    count is what bounds the SPMD layer latency (every device walks its
    own list; the layer finishes when the slowest one does). Requires
    ``wl.shard_of``; ``num_shards`` widens the count vector past the
    highest assigned id (devices holding no blocks count zero steps).
    """
    if wl.shard_of is None:
        raise ValueError("work list carries no shard assignment "
                         "(build_worklist(..., shard_of=...))")
    d = num_shards if num_shards is not None \
        else int(wl.shard_of.max(initial=0)) + 1
    per_pair = np.maximum(np.asarray(wl.steps_per_pair, np.int64), 1)
    return np.bincount(wl.shard_of, weights=per_pair.sum(axis=1),
                       minlength=d).astype(np.int64)


def shard_imbalance(counts: np.ndarray) -> float:
    """max/mean - 1 of the per-device step counts (0.0 = perfect §4
    balance; the committed bound is :data:`SHARD_BALANCE_TOL`)."""
    counts = np.asarray(counts, np.float64)
    if counts.size <= 1 or counts.sum() == 0:
        return 0.0
    return float(counts.max() / counts.mean() - 1.0)


def shard_scaling_efficiency(counts: np.ndarray) -> float:
    """Deterministic step-count scaling efficiency of a sharded schedule:
    ``total_steps / (D * max_per_device_steps)`` — the fraction of ideal
    D-way speedup the balance actually delivers (1.0 = perfectly even).
    Wall-clock is reported but never gated (repo policy); this is the
    machine-independent quantity the dist-vision gate holds."""
    counts = np.asarray(counts, np.float64)
    if counts.size == 0 or counts.max() == 0:
        return 1.0
    return float(counts.sum() / (counts.size * counts.max()))


def shard_worklist_args(wl: WorkList, num_shards: int
                        ) -> Dict[str, np.ndarray]:
    """Split a sharded flat schedule into per-device streams for the SPMD
    executor (each device walks only its own n-blocks, with n reindexed to
    the device-local block range).

    Requires a *contiguous* assignment (``shard_of`` non-decreasing with
    equal block counts per device — what the packer's fold-legal shard
    permutation produces), because the device-local n index is then just
    ``n - d * (nb // D)`` and concatenating per-device output slabs in
    ring order reassembles the full N axis exactly.

    Only live entries are kept (the XLA executor's flush-only elision);
    streams pad to the longest device's length with entries routed to the
    discard segment (``valid == 0``), so the stacked arrays shard evenly
    over the mesh's model axis. Returns ``n/m/k/j/valid [D, Tmax]`` int32.
    """
    if wl.shard_of is None:
        raise ValueError("work list carries no shard assignment")
    if wl.nb % num_shards:
        raise ValueError(f"nb={wl.nb} not divisible by D={num_shards}")
    nbl = wl.nb // num_shards
    expect = np.repeat(np.arange(num_shards), nbl)
    if not np.array_equal(np.asarray(wl.shard_of), expect):
        raise ValueError("SPMD execution needs the contiguous equal-count "
                         "shard assignment (the packer's fold-legal form)")
    live = wl.k >= 0
    dev = wl.shard_of[wl.n]
    tmax = max(int(np.max(np.bincount(dev[live], minlength=num_shards),
                          initial=0)), 1)
    out = {f: np.zeros((num_shards, tmax), np.int32)
           for f in ("n", "m", "k", "j", "valid")}
    for d in range(num_shards):
        sel = np.nonzero(live & (dev == d))[0]
        t = sel.size
        out["n"][d, :t] = wl.n[sel] - d * nbl
        out["m"][d, :t] = wl.m[sel]
        out["k"][d, :t] = wl.k[sel]
        out["j"][d, :t] = wl.j[sel]
        out["valid"][d, :t] = 1
    return out


# ---------------------------------------------------------------------------
# pure-jnp schedule model (no kernel launch, jit-safe — the serving probes
# and the autotuner score with this; tests pin it to build_worklist exactly)
# ---------------------------------------------------------------------------
def schedule_stats(patches: Optional[jnp.ndarray], indices: jnp.ndarray, *,
                   bk: int, bm_rows: int = DEFAULT_BM,
                   occ: Optional[jnp.ndarray] = None,
                   mb: Optional[int] = None,
                   gate_indices: Optional[jnp.ndarray] = None
                   ) -> Dict[str, jnp.ndarray]:
    """Pure-jnp model of the telescoped work-list schedule (no kernel).

    Predicts, at (n-block, m-block, k-chunk) grid granularity, the steps
    the compacted schedule runs: ``live_chunk_steps`` = stored weight
    chunk ∧ occupied activation block (the §3.2 intersection; the union
    over both streams when ``gate_indices`` is given), ``dead_pairs`` =
    (n, m) pairs with no live chunk (each degenerates to one flush-only
    step), ``scheduled_steps`` = live + flush-only, and
    ``dense_grid_steps`` = what the predicated dense grid schedules.
    Pinned to :func:`build_worklist`'s actual step counts by tests, so
    benches and serving probes report schedule compaction without
    building work lists in the hot loop.

    Instead of ``patches`` the caller may pass the block-occupancy map
    directly (``occ`` bool [mb, kb]) or — for the *static* pack-time
    schedule, where every activation block counts as live — just ``mb``.
    This is what the autotuner scores candidate tile configs with: the
    occupancy stays O(mb * kb) per candidate instead of re-materializing
    an O(M * K) patch matrix per (bm, bn) point.
    """
    if patches is not None:
        M, K = patches.shape
        mb, kb = M // bm_rows, K // bk
        occ = (patches.reshape(mb, bm_rows, kb, bk) != 0).any(axis=(1, 3))
    elif occ is not None:
        occ = jnp.asarray(occ, bool)
        mb, kb = occ.shape
    else:
        if mb is None:
            raise ValueError("need patches, occ, or mb")
        kb = int(jnp.max(indices) + 1) if indices.size else 1
        occ = jnp.ones((mb, max(kb, 1)), bool)

    def live_of(idx):
        valid = idx >= 0
        safe = jnp.where(valid, idx, 0)
        return valid[:, None, :] & occ[:, safe].transpose(1, 0, 2)

    live = live_of(indices)                                  # [nb, mb, nz]
    if gate_indices is not None:
        live = live | live_of(gate_indices)
    nb, max_nz = indices.shape
    live_steps = live.sum()
    dead_pairs = (live.sum(-1) == 0).sum()
    return {"live_chunk_steps": live_steps,
            "dead_pairs": dead_pairs,
            "scheduled_steps": live_steps + dead_pairs,
            "dense_grid_steps": jnp.int32(nb * mb * max_nz)}


def schedule_counters(wl: WorkList, *,
                      predicated_steps: Optional[int] = None,
                      combine: bool = False,
                      mb_per_img: Optional[int] = None,
                      mesh: bool = False,
                      num_shards: Optional[int] = None) -> Dict[str, float]:
    """The unified schedule-counters record both serving layers report.

    ``predicated_steps`` (optional) is the step count of the in-lane
    predicated kernel this schedule replaces — for the FFN decode path
    that is the dense grid at ``sub_m`` sub-block granularity over the
    128-row-padded batch, which is what makes the decode compaction
    factor honest about what the old kernel actually iterated.

    ``combine=True`` adds the cross-request telescoped fetch-plan
    counters (:meth:`WorkList.combined` at ``mb_per_img`` granularity,
    defaulting to the build-time value): schedule chunk reads, the
    per-image-dedup baseline (per-request sequential serving), the
    batch-wide deduped fetches, and the resulting
    ``cross_request_combine_factor``.

    ``mesh=True`` adds the per-shard balance counters of a cluster-sharded
    schedule (requires ``wl.shard_of``): ``num_devices``,
    ``per_device_steps``, ``step_imbalance`` (max/mean - 1, bound by
    :data:`SHARD_BALANCE_TOL`), and ``step_scaling_efficiency``
    (total / (D * max) — the gated, machine-independent scaling number).
    """
    rec = {"scheduled_steps": wl.num_steps,
           "live_chunk_steps": wl.mac_steps,
           "flush_only_steps": wl.flush_only_steps,
           "dense_grid_steps": wl.dense_grid_steps}
    if predicated_steps is not None:
        rec["predicated_grid_steps"] = int(predicated_steps)
        rec["compaction_factor"] = predicated_steps / max(wl.num_steps, 1)
    if combine:
        cs = wl.combined(mb_per_img)
        rec["filter_chunk_requests"] = cs.requests
        rec["per_image_filter_fetches"] = cs.per_image_fetches
        rec["combined_filter_fetches"] = cs.num_fetches
        rec["images"] = cs.images
        rec["cross_request_combine_factor"] = \
            cs.cross_request_combine_factor
    if mesh:
        counts = per_shard_steps(wl, num_shards)
        rec["num_devices"] = int(counts.size)
        rec["per_device_steps"] = [int(c) for c in counts]
        rec["step_imbalance"] = shard_imbalance(counts)
        rec["step_scaling_efficiency"] = shard_scaling_efficiency(counts)
    return rec


# ---------------------------------------------------------------------------
# the Pallas walker (grid = the flat work list)
# ---------------------------------------------------------------------------
def tile_dot(x, w):
    """One fp32 (bm, bk) x (bk, bn) tile MAC, shared by every Pallas kernel.
    ``HIGHEST`` keeps the MXU at full fp32 on TPU (its default is a
    reduced-precision pass); on CPU an fp32 dot is fp32 either way."""
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def batched_tile_dot(xg, wg):
    """The XLA executors' [T, bm, bk] x [T, bk, bn] batched tile GEMM, at
    the same fp32 precision as :func:`tile_dot`."""
    return jax.lax.dot_general(
        xg.astype(jnp.float32), wg.astype(jnp.float32),
        (((2,), (1,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def tile_occupancy(y, sub_m: int):
    """In-kernel occupancy of one (bm, bn) output tile: an int32 (bm //
    sub_m, 1) column, 1 where a ``sub_m``-row sub-block holds a non-zero.
    Every intermediate stays 2-D or 3-D with kept dims, which is what the
    TPU's kernel compiler lays out (a 1-D reduction result is not)."""
    bm, bn = y.shape
    nz = (y != 0).astype(jnp.int32).reshape(bm // sub_m, sub_m, bn)
    return jnp.max(jnp.max(nz, axis=1), axis=1, keepdims=True)


def occupancy_out_shape(nb: int, mb: int, nsub: int):
    """The kernels' emitted occupancy array: one (nsub, 1) block per (n, m)
    output tile, whole trailing dims so every block is layout-legal."""
    return jax.ShapeDtypeStruct((nb, mb, nsub, 1), jnp.int32)


def occupancy_rows(occ):
    """[nb, mb, nsub, 1] emitted blocks -> the [M // sub_m, nb] occupancy
    map every caller reads (row sub-block major, one column per n block)."""
    nb, mb, nsub, _ = occ.shape
    return occ[..., 0].transpose(1, 2, 0).reshape(mb * nsub, nb)


def _walk_kernel(*args, streams: int, ncolors: int, mb_per_img: int,
                 sub_m: int, bm_rows: int, act: Optional[str],
                 emit_occupancy: bool):
    args = list(args)
    n_ref = args.pop(0)
    m_ref = args.pop(0)
    k_ref = args.pop(0)
    j_ref = args.pop(0)
    first_ref = args.pop(0)
    last_ref = args.pop(0)
    k2_ref = args.pop(0) if streams == 2 else None
    x_ref, w_ref = args.pop(0), args.pop(0)
    if streams == 2:
        x2_ref, w2_ref = args.pop(0), args.pop(0)
    o_ref = args.pop(0)
    occ_out_ref = args.pop(0) if emit_occupancy else None
    acc_ref = args.pop(0)                 # (ncolors, bm, bn): §3.3 colors
    acc2_ref = args.pop(0) if streams == 2 else None
    t = pl.program_id(0)
    parity = (m_ref[t] // mb_per_img) % ncolors

    @pl.when(first_ref[t] == 1)
    def _init():
        acc_ref[parity] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)
        if acc2_ref is not None:
            acc2_ref[parity] = jnp.zeros(acc2_ref.shape[1:], acc2_ref.dtype)

    @pl.when(k_ref[t] >= 0)
    def _mac():
        # a scheduled step is a live chunk by construction: one dense MXU
        # tile MAC, nothing left to predicate in-lane
        acc_ref[parity] += tile_dot(x_ref[...], w_ref[0, 0])

    if streams == 2:
        @pl.when(k2_ref[t] >= 0)
        def _mac2():
            acc2_ref[parity] += tile_dot(x2_ref[...], w2_ref[0, 0])

    @pl.when(last_ref[t] == 1)
    def _flush():
        g = acc2_ref[parity] if acc2_ref is not None else None
        y = activate(acc_ref[parity], g, act)
        o_ref[...] = y.astype(o_ref.dtype)
        if occ_out_ref is not None:
            occ_out_ref[...] = tile_occupancy(y, sub_m)


@functools.partial(jax.jit, static_argnames=(
    "streams", "bk", "bn", "bm_rows", "sub_m", "mb_per_img", "ncolors",
    "nb", "act", "emit_occupancy", "interpret"))
def _worklist_spmm_pallas(patches, vals, vals2, *wl_args, streams, bk, bn,
                          bm_rows, sub_m, mb_per_img, ncolors, nb, act,
                          emit_occupancy, interpret):
    M, K = patches.shape
    T = wl_args[0].shape[0]
    S = 6 + (streams - 1)                 # prefetched schedule arrays
    kernel = functools.partial(
        _walk_kernel, streams=streams, ncolors=ncolors,
        mb_per_img=mb_per_img, sub_m=sub_m, bm_rows=bm_rows, act=act,
        emit_occupancy=emit_occupancy)

    def x_spec(which):
        return pl.BlockSpec(
            (bm_rows, bk),
            lambda t, n, m, k, j, f, l, *rest, _w=which:
            (m[t], jnp.maximum((k, *rest)[_w][t], 0)))

    w_spec = pl.BlockSpec((1, 1, bk, bn),
                          lambda t, n, m, k, j, f, l, *rest:
                          (n[t], jnp.maximum(j[t], 0), 0, 0))
    in_specs = [x_spec(0), w_spec]
    operands = (patches, vals)
    scratch = [pltpu.VMEM((ncolors, bm_rows, bn), jnp.float32)]
    if streams == 2:
        in_specs += [x_spec(1), w_spec]
        operands = operands + (patches, vals2)
        scratch.append(pltpu.VMEM((ncolors, bm_rows, bn), jnp.float32))
    out_shape = [jax.ShapeDtypeStruct((M, nb * bn), patches.dtype)]
    out_specs = [pl.BlockSpec((bm_rows, bn),
                              lambda t, n, m, k, j, f, l, *rest:
                              (m[t], n[t]))]
    if emit_occupancy:
        nsub = bm_rows // sub_m
        out_shape.append(occupancy_out_shape(nb, M // bm_rows, nsub))
        out_specs.append(pl.BlockSpec(
            (None, None, nsub, 1),
            lambda t, n, m, k, j, f, l, *rest: (n[t], m[t], 0, 0)))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=S,        # the flat work list
            grid=(T,),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(*wl_args, *operands)
    if emit_occupancy:
        return out[0], occupancy_rows(out[1])
    return tuple(out)


# ---------------------------------------------------------------------------
# the XLA executor (gather scheduled pairs -> batched GEMM -> segment-sum)
# ---------------------------------------------------------------------------
def segment_spmm(prods, pair, *, nb, mb, bm_rows, bn, M, out_dtype,
                 act: Optional[str], sub_m: int, emit_occupancy: bool):
    """Shared tail of every XLA work-list executor: segment-sum the
    per-step tile products per (n, m) pair *in schedule order* (the same
    fp32 accumulation order as the Pallas walker — bit-identical), apply
    the activation epilogue, and lay the pair grid back out as [M, N].

    ``prods`` is one [T, bm, bn] product stream or a (stream, stream2)
    tuple (the gated FFN's two accumulators), with ``pair`` the matching
    segment ids (a tuple too in the two-stream case).
    """
    if isinstance(prods, tuple):
        (p1, p2), (pair1, pair2) = prods, pair
        acc = jax.ops.segment_sum(p1, pair1, num_segments=nb * mb)
        acc2 = jax.ops.segment_sum(p2, pair2, num_segments=nb * mb)
        acc = activate(acc, acc2, act)
    else:
        acc = jax.ops.segment_sum(prods, pair, num_segments=nb * mb)
        acc = activate(acc, None, act)
    out = acc.reshape(nb, mb, bm_rows, bn).transpose(1, 2, 0, 3) \
             .reshape(M, nb * bn).astype(out_dtype)
    res = [out]
    if emit_occupancy:
        res.append((out.reshape(M // sub_m, sub_m, nb, bn) != 0)
                   .any(axis=(1, 3)).astype(jnp.int32))
    return tuple(res)


def _gather_dot(patches, vals, wl_m, wl_k, wl_n, wl_j, *, bk, bm_rows, mb):
    """Gather exactly the scheduled (x block, W chunk) tile pairs and run
    one batched GEMM over them — the live half of the XLA executor."""
    M, K = patches.shape
    kb = K // bk
    x4 = patches.reshape(mb, bm_rows, kb, bk)
    xg = x4[wl_m, :, wl_k, :]                     # [T, bm, bk]
    wg = vals[wl_n, wl_j]                         # [T, bk, bn]
    return batched_tile_dot(xg, wg)               # [T, bm, bn]


@functools.partial(jax.jit, static_argnames=(
    "streams", "bk", "bn", "bm_rows", "sub_m", "nb", "mb", "act",
    "emit_occupancy"))
def _worklist_spmm_xla(patches, vals, vals2, s1_n, s1_m, s1_k, s1_j, s2_n,
                       s2_m, s2_k, s2_j, *, streams, bk, bn, bm_rows, sub_m,
                       nb, mb, act, emit_occupancy):
    """XLA executor of the compacted work list (non-TPU backends).

    The caller passes only the *live* entries per stream:
    ``segment_sum`` already yields zeros for pairs with no scheduled
    MACs, so flush-only steps (a Pallas grid necessity — its output
    blocks must be written) cost nothing here.
    """
    M, K = patches.shape
    prod = _gather_dot(patches, vals, s1_m, s1_k, s1_n, s1_j, bk=bk,
                       bm_rows=bm_rows, mb=mb)
    pair = s1_n * mb + s1_m
    if streams == 2:
        prod2 = _gather_dot(patches, vals2, s2_m, s2_k, s2_n, s2_j, bk=bk,
                            bm_rows=bm_rows, mb=mb)
        pair2 = s2_n * mb + s2_m
        return segment_spmm((prod, prod2), (pair, pair2), nb=nb,
                            mb=mb, bm_rows=bm_rows, bn=bn, M=M,
                            out_dtype=patches.dtype, act=act, sub_m=sub_m,
                            emit_occupancy=emit_occupancy)
    return segment_spmm(prod, pair, nb=nb, mb=mb, bm_rows=bm_rows, bn=bn,
                        M=M, out_dtype=patches.dtype, act=act, sub_m=sub_m,
                        emit_occupancy=emit_occupancy)


def worklist_spmm_padded(patches: jnp.ndarray, vals: jnp.ndarray,
                         wl_n: jnp.ndarray, wl_m: jnp.ndarray,
                         wl_k: jnp.ndarray, wl_j: jnp.ndarray,
                         valid: jnp.ndarray, *, bk: int, bn: int,
                         bm_rows: int, nb_local: int, mb: int,
                         act: Optional[str] = None) -> jnp.ndarray:
    """Device-local walk of one padded per-device schedule stream (from
    :func:`shard_worklist_args`) — the SPMD form of the XLA executor,
    traceable inside ``shard_map`` where entry counts must be static and
    equal across devices.

    Padding entries (``valid == 0``) gather a clamped-but-real tile pair
    and route their product to a discard segment past the pair grid, so
    they cost a step but never touch the output — each real pair still
    accumulates its live chunks in ascending-``j`` schedule order, which
    keeps the per-device output slab bitwise equal to the matching column
    block of the single-device executor. Returns ``[M, nb_local * bn]``.
    """
    M, K = patches.shape
    kb = K // bk
    nc = jnp.clip(wl_n, 0, nb_local - 1)
    mc = jnp.clip(wl_m, 0, mb - 1)
    kc = jnp.clip(wl_k, 0, kb - 1)
    jc = jnp.maximum(wl_j, 0)
    prod = _gather_dot(patches, vals, mc, kc, nc, jc, bk=bk,
                       bm_rows=bm_rows, mb=mb)
    pair = jnp.where(valid > 0, nc * mb + mc, nb_local * mb)
    acc = jax.ops.segment_sum(prod, pair,
                              num_segments=nb_local * mb + 1)[:-1]
    acc = activate(acc, None, act)
    return acc.reshape(nb_local, mb, bm_rows, bn).transpose(1, 2, 0, 3) \
              .reshape(M, nb_local * bn).astype(patches.dtype)


def worklist_spmm(patches: jnp.ndarray, vals: jnp.ndarray, wl: WorkList, *,
                  vals2: Optional[jnp.ndarray] = None, bk: int = LANE,
                  bn: int = LANE, bm_rows: int = DEFAULT_BM,
                  sub_m: Optional[int] = None,
                  mb_per_img: Optional[int] = None, ncolors: int = 1,
                  act: Optional[str] = None, emit_occupancy: bool = False,
                  interpret: Optional[bool] = None,
                  executor: Optional[str] = None):
    """Run a compacted :class:`WorkList` schedule — the shared walker every
    frontend dispatches to.

    ``patches [M, K] @ vals`` (+ ``vals2`` for the gated second stream),
    exactly ``wl.num_steps`` scheduled steps — ``wl.mac_steps`` live-chunk
    MACs plus one flush-only step per dead (n, m) pair. ``executor``
    picks the backend that walks the list (``"pallas"`` or ``"xla"``,
    ``None`` resolves per backend via :func:`resolve_executor`); outputs
    are bit-identical across executors (pinned per frontend).  ``ncolors``
    > 1 enables the §3.3 output-buffer coloring keyed by image parity
    (``mb_per_img`` row blocks per image); ``act`` is the fused
    activation epilogue; ``emit_occupancy`` adds the in-kernel activation
    bitmask output. Returns a tuple: ``(out [M, nb*bn][, occupancy])``.
    """
    executor = resolve_executor(executor)
    streams = 2 if vals2 is not None else 1
    assert (wl.k2 is not None) == (streams == 2), \
        "gated executor needs a two-stream work list (gate_indices)"
    sub_m = bm_rows if sub_m is None else sub_m
    M = patches.shape[0]
    mb = M // bm_rows
    mb_per_img = mb if mb_per_img is None else mb_per_img
    assert wl.mb == mb, (wl.mb, mb)
    if executor == "xla":
        def stream_args(ks):
            live = ks >= 0                # flush-only steps are free in XLA
            return tuple(jnp.asarray(a[live])
                         for a in (wl.n, wl.m, ks, wl.j))
        s1 = stream_args(wl.k)
        s2 = stream_args(wl.k2) if streams == 2 else \
            (jnp.zeros((0,), jnp.int32),) * 4
        return _worklist_spmm_xla(
            patches, vals, vals2 if vals2 is not None else vals,
            s1[0], s1[1], s1[2], s1[3], s2[0], s2[1], s2[2], s2[3],
            streams=streams, bk=bk, bn=bn, bm_rows=bm_rows, sub_m=sub_m,
            nb=wl.nb, mb=mb, act=act, emit_occupancy=emit_occupancy)
    return _worklist_spmm_pallas(
        patches, vals, vals2 if vals2 is not None else vals,
        *wl.prefetch_args(), streams=streams, bk=bk, bn=bn, bm_rows=bm_rows,
        sub_m=sub_m, mb_per_img=mb_per_img, ncolors=ncolors, nb=wl.nb,
        act=act, emit_occupancy=emit_occupancy,
        interpret=resolve_interpret(interpret))
