"""Pallas TPU kernel: implicit-GEMM two-sided sparse conv2d (BARISTA on CNNs).

The paper's workload is pruned CNNs with ReLU feature maps. This kernel runs
a whole conv layer as the paper's matrix interface: activations are
linearized to im2col patch rows (``jax.lax.conv_general_dilated_patches``)
and tiled against bitmask-packed pruned filter chunks — the same
chunk-block-sparse layout and row-sub-block skip machinery as
:mod:`repro.kernels.bitmask_spmm` (``subblock_macs`` is imported from there,
so the skip predicate is literally the same circuit).

Two schedules drive the layer:

* **Telescoped work-list schedule (default)** — the paper's §3.2 insight
  applied to the grid itself: sparsity is exploited by *not scheduling*
  dead work, not by predicating it away in-lane. At pack time (weights) or
  call time (activations, eager only) the per-``(n_block, m_block)``
  intersection of the stored filter chunk list with the activation-chunk
  occupancy is compacted into a :class:`~repro.kernels.bitmask_spmm.\
ConvWorkList` and the Pallas grid is the *flat work list* — one grid step
  per live chunk, dead row blocks degenerating to a flush-only step. Each
  scheduled step is a full dense (bm, bk) x (bk, bn) MXU tile MAC: the MXU
  is a dense systolic array, so once a tile is *scheduled* there is
  nothing left to predicate. The same work list can be executed by an
  XLA gather + batched-GEMM + segment-sum pipeline
  (``executor="xla"``) — bit-identical outputs — which is what non-TPU
  backends use so wall-clock sparsity wins do not depend on Pallas
  interpret mode.
* **Dense-grid schedule (``schedule="dense"``)** — the original
  ``(nb, mb, max_nz)`` grid with in-lane predication (``subblock_macs``):
  keeps the instrumented counters (``count_macs``) and the ``sub_m``-row
  occupancy skip, so it remains the measurement path the skip statistics
  come from. Tests pin both schedules bitwise-equal.

On top of the spmm core, the conv kernels add the CNN-specific pieces:

* **Fused ReLU epilogue** — the nonlinearity is applied to the fp32 VMEM
  accumulator at the flush, so the *activated* feature map goes to HBM in
  one pass and its zeros are real zeros the next layer can skip.
* **In-kernel occupancy emission** — the flush also writes the next layer's
  activation tile bitmask (``sub_m``-row × ``bn``-column occupancy of the
  post-ReLU output), so the measured feature-map density used by the
  simulator feedback loop comes from the same tensors the kernel produced,
  not a separate O(MN) host pass.
* **Output-buffer coloring (paper §3.3)** — output tiles are
  double-buffered: one (2, bm, bn) VMEM accumulator, the color selected by
  the *parity of the image* a row block belongs to. Consecutive input maps
  of a batch use alternating colors, so image ``i+1`` can start
  accumulating while image ``i``'s tiles drain — the barrier-free advance
  between consecutive input maps. Correctness is invariant to
  interleaving, which ``tests/test_vision.py`` pins (batched ==
  per-image sequential, bitwise) for both schedules.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import bitmask as bm
from repro.core.sparse import Padding, Stride, normalize_padding, \
    normalize_stride
from repro.kernels.bitmask_spmm import (count_output, count_rows,
                                       flat_occupancy, flush_count,
                                       subblock_macs)
from repro.kernels.worklist_core import (  # noqa: F401  (re-exports)
    DEFAULT_BM, LANE, ConvWorkList, activation_occupancy, batched_tile_dot,
    build_worklist, occupancy_out_shape, occupancy_rows, on_tpu,
    resolve_executor, resolve_interpret, schedule_counters, segment_spmm,
    tile_occupancy, worklist_spmm)


def _conv_kernel(idx_ref, occ_ref, x_ref, w_ref, *refs, nsteps: int,
                 two_sided: bool, sub_m: int, bm_rows: int, kb: int,
                 mb_per_img: int, fuse_relu: bool, emit_occupancy: bool,
                 count_macs: bool):
    refs = list(refs)
    o_ref = refs.pop(0)
    occ_out_ref = refs.pop(0) if emit_occupancy else None
    cntout_ref = refs.pop(0) if count_macs else None
    acc_ref = refs.pop(0)                       # (2, bm, bn): §3.3 colors
    cnt_ref = refs.pop(0) if count_macs else None

    n_i = pl.program_id(0)
    m_i = pl.program_id(1)
    j = pl.program_id(2)
    # output-buffer color: parity of the image this row block belongs to
    parity = (m_i // mb_per_img) % 2

    @pl.when(j == 0)
    def _init():
        acc_ref[parity] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)
        if cnt_ref is not None:
            cnt_ref[0] = 0

    k_idx = idx_ref[n_i, j]
    # MAC into the accumulator of this image's color (single call — the
    # color is a dynamic index, not a predicated pair of calls)
    subblock_macs(k_idx >= 0, jnp.maximum(k_idx, 0), occ_ref, m_i, x_ref,
                  w_ref[0, 0], acc_ref, cnt_ref, two_sided=two_sided,
                  sub_m=sub_m, bm=bm_rows, kb=kb, color=parity)

    @pl.when(j == nsteps - 1)
    def _flush():
        y = acc_ref[parity]
        if fuse_relu:
            y = jnp.maximum(y, 0.0)
        o_ref[...] = y.astype(o_ref.dtype)
        if occ_out_ref is not None:
            # next layer's activation tile bitmask: sub_m-row occupancy of
            # the post-epilogue output tile, one column per n block
            occ_out_ref[...] = tile_occupancy(y, sub_m)
        if cntout_ref is not None:
            flush_count(cntout_ref, cnt_ref)


@functools.partial(jax.jit, static_argnames=("bk", "bn", "bm_rows", "sub_m",
                                             "mb_per_img", "two_sided",
                                             "fuse_relu", "emit_occupancy",
                                             "interpret", "count_macs"))
def sparse_conv_spmm(patches: jnp.ndarray, indices: jnp.ndarray,
                     vals: jnp.ndarray, *, bk: int = LANE, bn: int = LANE,
                     bm_rows: int = DEFAULT_BM, sub_m: Optional[int] = None,
                     mb_per_img: Optional[int] = None, two_sided: bool = True,
                     fuse_relu: bool = True, emit_occupancy: bool = False,
                     interpret: Optional[bool] = None,
                     count_macs: bool = False):
    """Dense-grid implicit-GEMM core: ``patches [M, K] @ W [K, N]`` + fused
    epilogue, with in-lane predication (the instrumented measurement path).

    ``patches`` stacks the per-image im2col rows, each image padded to a
    whole number of ``bm_rows`` blocks (``mb_per_img`` blocks per image —
    the coloring key). Weights are the chunk-block-sparse layout of
    :class:`repro.core.bitmask.BlockSparseMatrix`.

    ``interpret=None`` resolves from the backend at call time
    (:func:`repro.kernels.worklist_core.resolve_interpret`) like every
    other kernel — compiled on TPU, interpreter elsewhere.

    Returns ``out [M, N]`` (x.dtype, fp32 accumulation, ReLU fused when
    ``fuse_relu``), plus an int32 ``[M // sub_m, n_blocks]`` occupancy map
    when ``emit_occupancy`` and an int32 ``[n_blocks, M // bm_rows]``
    executed-MAC map when ``count_macs`` (in that order).
    """
    interpret = resolve_interpret(interpret)
    M, K = patches.shape
    nb, max_nz = indices.shape
    N = nb * bn
    sub_m = bm_rows if sub_m is None else sub_m
    mb = M // bm_rows
    mb_per_img = mb if mb_per_img is None else mb_per_img
    assert M % bm_rows == 0 and K % bk == 0, (M, K, bm_rows, bk)
    assert bm_rows % sub_m == 0, (bm_rows, sub_m)
    assert mb % mb_per_img == 0, (mb, mb_per_img)

    occ = flat_occupancy(patches, sub_m, bk)

    grid = (nb, mb, max_nz)
    kernel = functools.partial(
        _conv_kernel, nsteps=max_nz, two_sided=two_sided, sub_m=sub_m,
        bm_rows=bm_rows, kb=K // bk, mb_per_img=mb_per_img,
        fuse_relu=fuse_relu, emit_occupancy=emit_occupancy,
        count_macs=count_macs)

    out_shape = [jax.ShapeDtypeStruct((M, N), patches.dtype)]
    out_specs = [pl.BlockSpec((bm_rows, bn), lambda n, m, j, idx, occ_: (m, n))]
    if emit_occupancy:
        nsub = bm_rows // sub_m
        out_shape.append(occupancy_out_shape(nb, mb, nsub))
        out_specs.append(pl.BlockSpec((None, None, nsub, 1),
                                      lambda n, m, j, idx, occ_: (n, m, 0, 0)))
    if count_macs:
        cnt_shape, cnt_spec = count_output(nb, mb)
        out_shape.append(cnt_shape)
        out_specs.append(cnt_spec)
    scratch = [pltpu.VMEM((2, bm_rows, bn), jnp.float32)]  # §3.3 colors
    if count_macs:
        scratch.append(pltpu.SMEM((1,), jnp.int32))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # indices, occupancy
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm_rows, bk),
                             lambda n, m, j, idx, occ_:
                             (m, jnp.maximum(idx[n, j], 0))),
                pl.BlockSpec((1, 1, bk, bn),
                             lambda n, m, j, idx, occ_: (n, j, 0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(indices, occ, patches, vals)
    out = list(out)
    if emit_occupancy:
        out[1] = occupancy_rows(out[1])
    if count_macs:
        out[-1] = count_rows(out[-1])
    return tuple(out)


# ---------------------------------------------------------------------------
# Telescoped work-list schedule (grid = the compacted list itself)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("bn", "bm_rows", "sub_m", "nb",
                                             "mb", "fuse_relu",
                                             "emit_occupancy"))
def _worklist_spmm_xla_slabs(slabs, vals, wl_slot, wl_m, wl_n, wl_j, *, bn,
                             bm_rows, sub_m, nb, mb, fuse_relu,
                             emit_occupancy):
    """The XLA work-list walker over *lazily extracted* chunk slabs.

    ``slabs [L, M, bk]`` holds only the K-chunks some scheduled step
    touches (:func:`extract_tap_slabs`); ``wl_slot`` maps each live step's
    ``wl.k`` to its slab row.  From the gather on, this is op-for-op the
    core XLA executor — same batched GEMM, same
    :func:`~repro.kernels.worklist_core.segment_spmm` tail — so outputs
    stay bit-identical to the full-patch executors while the dead
    1 - density of the im2col blow-up is never materialized (the lazy
    analogue of §3.2: dead *bytes*, like dead steps, simply never get
    scheduled).
    """
    L, M, bk = slabs.shape
    x4 = slabs.reshape(L, mb, bm_rows, bk)
    xg = x4[wl_slot, wl_m]                        # [T, bm, bk]
    wg = vals[wl_n, wl_j]                         # [T, bk, bn]
    prod = batched_tile_dot(xg, wg)               # [T, bm, bn]
    return segment_spmm(prod, wl_n * mb + wl_m, nb=nb, mb=mb,
                        bm_rows=bm_rows, bn=bn, M=M, out_dtype=slabs.dtype,
                        act="relu" if fuse_relu else None, sub_m=sub_m,
                        emit_occupancy=emit_occupancy)


def sparse_conv_spmm_wl(patches: jnp.ndarray, vals: jnp.ndarray,
                        wl: ConvWorkList, *, bk: int = LANE, bn: int = LANE,
                        bm_rows: int = DEFAULT_BM,
                        sub_m: Optional[int] = None,
                        mb_per_img: Optional[int] = None,
                        fuse_relu: bool = True, emit_occupancy: bool = False,
                        interpret: Optional[bool] = None,
                        executor: Optional[str] = None):
    """Work-list-scheduled implicit-GEMM core (the wall-clock path).

    A thin conv-flavored adapter over
    :func:`repro.kernels.worklist_core.worklist_spmm`: the §3.3
    image-parity output coloring (``ncolors=2``, keyed by ``mb_per_img``)
    and the fused-ReLU epilogue are the only things added on top of the
    shared walker. ``wl`` is the compacted schedule from
    :func:`repro.kernels.worklist_core.build_worklist`; exactly
    ``wl.num_steps`` grid steps run — ``wl.mac_steps`` live-chunk MACs
    plus one flush-only step per dead (n, m) pair. ``executor`` picks the
    backend that walks the list (pallas grid or XLA gather + batched GEMM
    + segment-sum; ``None`` resolves per backend via
    :func:`~repro.kernels.worklist_core.resolve_executor`), with outputs
    bit-identical across executors and vs the dense-grid kernel — the
    property tests pin this.
    """
    return worklist_spmm(
        patches, vals, wl, bk=bk, bn=bn, bm_rows=bm_rows, sub_m=sub_m,
        mb_per_img=mb_per_img, ncolors=2, act="relu" if fuse_relu else None,
        emit_occupancy=emit_occupancy, interpret=interpret,
        executor=executor)


def _padded_input(x: jnp.ndarray, kh: int, kw: int, stride: Stride,
                  padding: Padding) -> Tuple[jnp.ndarray, int, int, int, int]:
    """Zero-pad ``x`` for the conv window; returns (xp, oh, ow, sh, sw)."""
    sh, sw = normalize_stride(stride)
    pad = normalize_padding(padding)
    b, H, W, cin = x.shape
    if isinstance(pad, str):
        pads = jax.lax.padtype_to_pads((H, W), (kh, kw), (sh, sw), pad)
    else:
        pads = pad
    xp = jnp.pad(x, ((0, 0), tuple(pads[0]), tuple(pads[1]), (0, 0)))
    H2, W2 = xp.shape[1], xp.shape[2]
    oh = (H2 - kh) // sh + 1
    ow = (W2 - kw) // sw + 1
    return xp, oh, ow, sh, sw


def conv_out_size(H: int, W: int, kh: int, kw: int, stride: Stride,
                  padding: Padding) -> Tuple[int, int]:
    """(OH, OW) for the layer geometry — host arithmetic, no arrays (the
    autotuner and the lazy path need the patch-row count before any
    extraction happens)."""
    sh, sw = normalize_stride(stride)
    pad = normalize_padding(padding)
    if isinstance(pad, str):
        pads = jax.lax.padtype_to_pads((H, W), (kh, kw), (sh, sw), pad)
    else:
        pads = pad
    H2 = H + pads[0][0] + pads[0][1]
    W2 = W + pads[1][0] + pads[1][1]
    return (H2 - kh) // sh + 1, (W2 - kw) // sw + 1


def extract_patches(x: jnp.ndarray, kh: int, kw: int, stride: Stride,
                    padding: Padding, *, strategy: str = "auto"
                    ) -> Tuple[jnp.ndarray, Tuple[int, int]]:
    """im2col rows for the implicit GEMM: [B, OH*OW, Cin*kh*kw] (+ (OH, OW)).

    All strategies are pure jax ops, so patch extraction fuses into
    whatever jit the caller runs under — the K-fold patch blow-up never
    crosses a host boundary:

    * ``"patches"`` — ``jax.lax.conv_general_dilated_patches``;
      channel-major feature order (cin, kh, kw), matching the
      ``w.transpose(2, 0, 1, 3)`` matrixization of the packing path.
      It is a one-hot convolution, run at ``HIGHEST`` precision so the
      TPU copies fp32 pixels exactly instead of rounding them through a
      reduced-precision MXU pass.
    * ``"slices"``  — kh*kw strided slices of the padded map, stacked and
      transposed to the same channel-major order; XLA:CPU fuses this ~2x
      better than the patches primitive.
    * ``"taps"``    — the same slices *without* the transpose: tap-major
      feature order (kh, kw, cin), matching ``layout="tap"`` packing
      (``w.reshape(kh*kw*cin, cout)``) — cheaper still, since the
      channel-major shuffle never materializes.
    * ``"auto"``    — patches on TPU, slices elsewhere (resolved at trace
      time, like the interpret/executor knobs).
    """
    if strategy == "auto":
        strategy = "patches" if on_tpu() else "slices"
    if strategy == "patches":
        sh, sw = normalize_stride(stride)
        pad = normalize_padding(padding)
        patches = jax.lax.conv_general_dilated_patches(
            x, (kh, kw), (sh, sw), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        b, oh, ow, f = patches.shape
        return patches.reshape(b, oh * ow, f), (oh, ow)
    if strategy not in ("slices", "taps"):
        raise ValueError(f"unknown im2col strategy {strategy!r}")
    b, H, W, cin = x.shape
    xp, oh, ow, sh, sw = _padded_input(x, kh, kw, stride, padding)
    parts = [xp[:, dy:dy + (oh - 1) * sh + 1:sh,
                dx:dx + (ow - 1) * sw + 1:sw, :]
             for dy in range(kh) for dx in range(kw)]
    p = jnp.stack(parts, axis=3)                  # [b, oh, ow, kh*kw, cin]
    if strategy == "slices":
        p = p.transpose(0, 1, 2, 4, 3)            # channel-major features
    return p.reshape(b, oh * ow, cin * kh * kw), (oh, ow)


def extract_tap_slabs(x: jnp.ndarray, kh: int, kw: int, stride: Stride,
                      padding: Padding, *, chunks: np.ndarray, bk: int,
                      m_pad: int) -> jnp.ndarray:
    """Lazy im2col: materialize only the *live* K-chunks of the tap-major
    patch matrix.

    In ``layout="tap"`` a K-chunk is one ``(tap, channel-group)`` pair, so
    its ``[M, bk]`` column slab is a single shifted strided slice of the
    padded input — no stack, no transpose, no dead-chunk bytes.  Returns
    ``[len(chunks), B * m_pad, bk]`` with each image's rows zero-padded to
    ``m_pad``; slab values are bitwise-identical to the corresponding
    columns of :func:`extract_patches` (any strategy), which is what keeps
    the lazy executor bit-equal to the full-patch ones.  ``chunks`` is a
    static (host) list — it comes from the pack-time work list.
    """
    b, H, W, cin = x.shape
    assert cin % bk == 0, (cin, bk)
    cpt = cin // bk                               # chunks per tap
    xp, oh, ow, sh, sw = _padded_input(x, kh, kw, stride, padding)
    m_img = oh * ow
    slabs = []
    for c in [int(c) for c in np.asarray(chunks)]:
        tap, sub = divmod(c, cpt)
        dy, dx = divmod(tap, kw)
        s = xp[:, dy:dy + (oh - 1) * sh + 1:sh,
               dx:dx + (ow - 1) * sw + 1:sw, sub * bk:(sub + 1) * bk]
        slabs.append(s.reshape(b, m_img, bk))
    p = jnp.stack(slabs, axis=0)                  # [L, b, m_img, bk]
    p = jnp.pad(p, ((0, 0), (0, 0), (0, m_pad - m_img), (0, 0)))
    return p.reshape(len(slabs), b * m_pad, bk)


def sparse_conv2d_nhwc(x: jnp.ndarray, w: bm.BlockSparseMatrix, kh: int,
                       kw: int, cout: int, *, stride: Stride = 1,
                       padding: Padding = "SAME", sub_m: int = 8,
                       two_sided: bool = True, fuse_relu: bool = True,
                       emit_occupancy: bool = False,
                       interpret: Optional[bool] = None,
                       count_macs: bool = False,
                       bm_rows: int = DEFAULT_BM,
                       schedule: str = "compact",
                       executor: Optional[str] = None,
                       im2col: str = "auto",
                       layout: str = "channel",
                       compact_activations: bool = False,
                       report_schedule: bool = False,
                       wl_cache: Optional[dict] = None):
    """One conv layer through the sparse kernel: x [B, H, W, Cin] -> [B, OH,
    OW, Cout] (ReLU fused when ``fuse_relu``).

    ``w`` packs the matrixized filters (``pack_conv_filters``): K =
    Cin*kh*kw padded to the chunk, N = Cout padded to the chunk. Each
    image's patch rows are padded to whole ``bm_rows`` blocks and stacked,
    so the kernel's coloring alternates accumulators between consecutive
    images.

    ``schedule="compact"`` (default) drives the grid from the telescoped
    work list (pack-time weight chunk lists; plus the activation-chunk
    intersection when ``compact_activations`` — eager calls only, the
    occupancy is data). ``schedule="dense"`` is the instrumented
    dense-grid path (required for ``count_macs``). ``executor`` and
    ``im2col`` select the work-list walker and the patch-extraction
    strategy (both resolve per backend when ``None``/default).

    ``layout`` must match how ``w`` was matrixized
    (:func:`repro.sparsity.conv.pack_conv_filters`): ``"channel"`` pairs
    with the ``patches``/``slices`` strategies, ``"tap"`` with ``taps``
    or ``lazy``.  ``im2col="lazy"`` (tap layout, compact schedule, XLA
    executor) materializes only the live K-chunk slabs named by the
    pack-time work list instead of the full im2col matrix; combinations
    that need the full patch matrix (dense grid, activation compaction,
    the Pallas walker) silently demote ``lazy`` to ``taps`` — slab
    values equal patch values bitwise, so the result is unchanged.

    Returns ``(out, aux)`` where ``aux`` carries the optional
    ``occupancy`` (int32 [B, ceil(M_img/sub_m), n_blocks], padded rows
    zero) and ``mac_counts`` outputs, the patch-matrix metadata the stats
    path reuses, and — for compact schedules or ``report_schedule`` — a
    ``schedule`` dict with scheduled vs dense-grid step counts.
    """
    interpret = resolve_interpret(interpret)
    if count_macs and schedule == "compact":
        # the executed-MAC counters live in the dense-grid kernel; keep
        # the promised aux["schedule"] by reporting the compact schedule
        schedule = "dense"
        report_schedule = True
    if layout == "tap":
        if im2col in ("auto", "patches", "slices"):
            im2col = "taps"
    elif im2col in ("taps", "lazy"):
        raise ValueError(f"im2col={im2col!r} needs layout='tap' packing")
    lazy = im2col == "lazy"
    if lazy and (schedule != "compact" or compact_activations
                 or resolve_executor(executor) != "xla"):
        im2col, lazy = "taps", False
    b = x.shape[0]
    if lazy:
        oh, ow = conv_out_size(x.shape[1], x.shape[2], kh, kw, stride,
                               padding)
        flat = None
    else:
        with jax.named_scope("im2col"):
            patches, (oh, ow) = extract_patches(x, kh, kw, stride, padding,
                                                strategy=im2col)
    m_img = oh * ow
    k_total = w.shape[0]
    pad_rows = (-m_img) % bm_rows
    m_pad = m_img + pad_rows
    if not lazy:
        pad_k = k_total - patches.shape[-1]
        assert pad_k >= 0, (patches.shape, k_total)
        with jax.named_scope("im2col"):
            patches = jnp.pad(patches, ((0, 0), (0, pad_rows), (0, pad_k)))
            flat = patches.reshape(b * m_pad, k_total)
    mb = (b * m_pad) // bm_rows
    aux = {"m_img": m_img, "k_total": k_total, "oh": oh, "ow": ow}

    wl = None
    if schedule == "compact" or report_schedule:
        occ_blk = None
        if compact_activations:
            if isinstance(flat, jax.core.Tracer):
                raise ValueError(
                    "compact_activations intersects the schedule with the "
                    "activation occupancy, which is data — eager (concrete) "
                    "calls only; under jit use the pack-time weight "
                    "compaction (compact_activations=False)")
            occ_blk = np.asarray(
                bm.chunk_occupancy(flat, bm_rows, w.bk))
        if occ_blk is None and wl_cache is not None:
            # static (pack-time) schedules depend only on the row-block
            # count, so repeat eager calls reuse the compacted list
            wl = wl_cache.get(mb)
        if wl is None:
            wl = build_worklist(w.host_indices(), mb, occ_blk=occ_blk,
                                mb_per_img=m_pad // bm_rows,
                                shard_of=getattr(w, "shard_of", None))
            if occ_blk is None and wl_cache is not None:
                wl_cache[mb] = wl
        aux["schedule"] = dict(
            schedule_counters(wl),        # the unified counters record
            activation_compacted=occ_blk is not None)
        if report_schedule:
            from repro.core.telescope import combine_schedule_requests
            # a fetch stays outstanding for ~one pair's sweep (the
            # weight-stationary reuse window)
            aux["schedule"]["combining"] = combine_schedule_requests(
                wl.k, fetch_latency=wl.num_steps / max(wl.num_pairs, 1))
            # §3.2 lifted across the batch: the exact deduped fetch plan
            cs = wl.combined()
            aux["schedule"]["cross_request"] = {
                "requests": cs.requests,
                "per_image_fetches": cs.per_image_fetches,
                "fetches": cs.num_fetches,
                "images": cs.images,
                "combine_factor": cs.cross_request_combine_factor,
            }
            if occ_blk is not None:
                # what the static (pack-time-only) schedule would run —
                # the compiled pipeline's grid size for this geometry
                wl_s = wl_cache.get(mb) if wl_cache is not None else None
                if wl_s is None:
                    wl_s = build_worklist(w.host_indices(), mb,
                                          mb_per_img=m_pad // bm_rows,
                                          shard_of=getattr(w, "shard_of",
                                                           None))
                    if wl_cache is not None:
                        wl_cache[mb] = wl_s
                aux["schedule"]["static_scheduled_steps"] = wl_s.num_steps
            else:
                aux["schedule"]["static_scheduled_steps"] = wl.num_steps

    with jax.named_scope("walker"):
        if lazy:
            live = wl.k >= 0
            union = np.unique(wl.k[live])
            if union.size == 0:
                M = b * m_pad
                out0 = jnp.zeros((M, w.n_blocks * w.bn), x.dtype)
                res = (out0,) + ((jnp.zeros((M // sub_m, w.n_blocks),
                                            jnp.int32),) if emit_occupancy
                                 else ())
            else:
                slot_of = np.zeros(k_total // w.bk, np.int32)
                slot_of[union] = np.arange(union.size, dtype=np.int32)
                with jax.named_scope("im2col"):
                    slabs = extract_tap_slabs(x, kh, kw, stride, padding,
                                              chunks=union, bk=w.bk,
                                              m_pad=m_pad)
                res = _worklist_spmm_xla_slabs(
                    slabs, w.vals, jnp.asarray(slot_of[wl.k[live]]),
                    jnp.asarray(wl.m[live]), jnp.asarray(wl.n[live]),
                    jnp.asarray(wl.j[live]), bn=w.bn, bm_rows=bm_rows,
                    sub_m=sub_m, nb=wl.nb, mb=mb, fuse_relu=fuse_relu,
                    emit_occupancy=emit_occupancy)
        elif schedule == "compact":
            res = sparse_conv_spmm_wl(
                flat, w.vals, wl, bk=w.bk, bn=w.bn, bm_rows=bm_rows,
                sub_m=sub_m, mb_per_img=m_pad // bm_rows, fuse_relu=fuse_relu,
                emit_occupancy=emit_occupancy, interpret=interpret,
                executor=executor)
        elif schedule == "dense":
            res = sparse_conv_spmm(
                flat, w.indices, w.vals, bk=w.bk, bn=w.bn, bm_rows=bm_rows,
                sub_m=sub_m, mb_per_img=m_pad // bm_rows, two_sided=two_sided,
                fuse_relu=fuse_relu, emit_occupancy=emit_occupancy,
                interpret=interpret, count_macs=count_macs)
        else:
            raise ValueError(f"unknown schedule {schedule!r}")
    out = res[0].reshape(b, m_pad, w.n_blocks * w.bn)
    out = out[:, :m_img, :cout].reshape(b, oh, ow, cout)
    i = 1
    if emit_occupancy:
        occ = res[i].reshape(b, m_pad // sub_m, w.n_blocks)
        aux["occupancy"] = occ[:, : -(-m_img // sub_m)]
        i += 1
    if count_macs:
        aux["mac_counts"] = res[i]
    return out, aux
