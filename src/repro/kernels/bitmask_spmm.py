"""Pallas TPU kernel: chunk-granular two-sided sparse matmul (BARISTA core).

The paper's PE matches non-zero positions per scalar with prefix-sum /
priority-encoder circuits. The TPU's MXU is a dense 128x128 systolic array,
so the TPU-native granularity for sparsity is the 128-wide *chunk* — exactly
the paper's chunk unit. This kernel computes ``x @ W`` where ``W`` is stored
chunk-block-sparse (only (k-chunk, n-block) tiles with any non-zero are
stored; see :class:`repro.core.bitmask.BlockSparseMatrix`) and, in the
two-sided mode, also skips tiles whose *activation* block is all-zero
(natural sparsity from ReLU-family nonlinearities — the paper's feature-map
sparsity).

Mapping of the paper's mechanisms:

* **FGR / IFGC grid** -> the Pallas grid: ``n``-blocks are the filter-group
  rows (each owns a filter shard), ``m``-blocks the input-map columns.
* **No broadcasts / barrier-free** -> each (m, n) grid cell walks only *its
  own* non-zero chunk list (scalar-prefetched indices); there is no
  synchronization between cells, and VMEM accumulators play the role of the
  colored output buffers (a cell proceeds to its next input tile without
  waiting for siblings).
* **Round-robin sub-chunk assignment** -> the host-side chunk schedule can be
  rotated per step (``core.balance.round_robin_assignment``); the kernel is
  oblivious, which is the point — the balancing is software, as in the paper.
* **Hierarchical buffering** -> BlockSpec tiles are the wide shared buffers
  (chunk-wide fetches from HBM); the fp32 VMEM accumulator is the narrow
  private buffer at the compute.
* **Row-sub-block occupancy** -> in two-sided mode the activation occupancy
  map is kept at ``sub_m``-row granularity *within* the ``bm``-row grid
  block, so a decode microbatch with one live lane (its row padded into an
  otherwise-zero 128-row block) only MACs its own ``sub_m`` rows instead of
  the whole block — the per-scalar skip of the paper's PE, quantized to the
  smallest MXU-legal row tile instead of the full block.

Weight-stationary dataflow ("snarfing" limit case): the W tile for (n, j) is
fetched once per m-sweep by Pallas' pipelined DMA and the m-innermost grid
order reuses it across input blocks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the work-list machinery lives in the unified core; these names stay
# importable from here for the pre-core call sites (tests, conv, autotune)
from repro.kernels.worklist_core import (  # noqa: F401  (re-exports)
    DEFAULT_BM, LANE, ConvWorkList, WorkList, activation_occupancy,
    build_worklist, resolve_interpret, tile_dot, worklist_spmm)


def subblock_macs(valid, k_safe, occ_ref, m_i, x_ref, w, acc_ref, cnt_ref, *,
                  two_sided: bool, sub_m: int, bm: int, kb: int, color=None):
    """MAC one (bm, bk) x (bk, bn) tile into ``acc_ref``.

    In two-sided mode the tile is processed as ``bm // sub_m`` row
    sub-blocks, each skipped when its occupancy bit (activation rows all
    zero) is clear — a single live decode lane does not force MACs for the
    other ``bm - sub_m`` rows of its block. ``occ_ref`` is the flattened
    [M // sub_m * kb] occupancy table (:func:`flat_occupancy`; 1-D, so the
    scalar memory holds it unpadded). ``cnt_ref`` (optional (1,) SMEM
    scratch) counts executed sub-block MACs (tile MACs when one-sided) so
    tests can assert the skip logic fires exactly. Shared with the fused
    FFN kernel (:mod:`repro.kernels.fused_ffn`).

    When ``color`` (a traced int32 scalar) is given, ``acc_ref`` carries a
    leading color axis — shape (ncolors, bm, bn) — and the MAC lands in
    ``acc_ref[color]``: the double-buffered output accumulators of the
    paper's §3.3 coloring, selected dynamically instead of duplicating the
    call per color.
    """
    def _acc(lo, size):
        if color is None:
            return (slice(lo, lo + size), slice(None))
        return (color, slice(lo, lo + size), slice(None))

    def _count():
        if cnt_ref is not None:
            cnt_ref[0] += 1

    if not two_sided:
        @pl.when(valid)
        def _mac():
            acc_ref[_acc(0, bm)] += tile_dot(x_ref[...], w)
            _count()
        return
    nsub = bm // sub_m
    base = m_i * nsub
    for si in range(nsub):
        live = jnp.logical_and(valid, occ_ref[(base + si) * kb + k_safe] > 0)

        @pl.when(live)
        def _mac(si=si):
            lo = si * sub_m
            acc_ref[_acc(lo, sub_m)] += tile_dot(x_ref[lo:lo + sub_m, :], w)
            _count()


def flat_occupancy(x: jnp.ndarray, sub_m: int, bk: int) -> jnp.ndarray:
    """:func:`activation_occupancy` flattened row-major to 1-D — the form
    the predicated kernels scalar-prefetch (a 2-D table's last dim pads to
    128 words in SMEM; 1-D does not)."""
    return activation_occupancy(x, sub_m, bk).reshape(-1)


def count_output(nb: int, mb: int):
    """(out_shape, out_spec) of the executed-MAC counters: one lane-wide
    (1, 128) row per (n, m) grid cell, so each block spans whole trailing
    dims. :func:`count_rows` reads the [nb, mb] map back out."""
    return (jax.ShapeDtypeStruct((nb, mb, 1, LANE), jnp.int32),
            pl.BlockSpec((None, None, 1, LANE),
                         lambda n, m, j, *_: (n, m, 0, 0)))


def flush_count(cntout_ref, cnt_ref):
    """Write the cell's SMEM MAC counter into its count-output row."""
    cntout_ref[...] = jnp.full(cntout_ref.shape, cnt_ref[0], jnp.int32)


def count_rows(cnt: jnp.ndarray) -> jnp.ndarray:
    """[nb, mb, 1, 128] counter rows -> the int32 [nb, mb] map."""
    return cnt[:, :, 0, 0]


# ---------------------------------------------------------------------------
# Telescoped work-list compaction (BARISTA §3.2 applied to the grid)
# ---------------------------------------------------------------------------
# build_worklist / ConvWorkList / the walkers now live in
# repro.kernels.worklist_core (imported above); what stays here is the
# dense-grid predicated kernel — the instrumented measurement path — and
# the FFN-shaped work-list variant below.
def _kernel(idx_ref, occ_ref, x_ref, w_ref, *refs, nsteps: int,
            two_sided: bool, sub_m: int, bm: int, kb: int, count_macs: bool):
    if count_macs:
        o_ref, cntout_ref, acc_ref, cnt_ref = refs
    else:
        o_ref, acc_ref = refs
        cntout_ref = cnt_ref = None
    n_i = pl.program_id(0)
    m_i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if cnt_ref is not None:
            cnt_ref[0] = 0

    k_idx = idx_ref[n_i, j]
    subblock_macs(k_idx >= 0, jnp.maximum(k_idx, 0), occ_ref, m_i, x_ref,
                  w_ref[0, 0], acc_ref, cnt_ref, two_sided=two_sided,
                  sub_m=sub_m, bm=bm, kb=kb)

    @pl.when(j == nsteps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)
        if cntout_ref is not None:
            flush_count(cntout_ref, cnt_ref)


@functools.partial(jax.jit, static_argnames=("bk", "bn", "bm", "sub_m",
                                             "two_sided", "interpret",
                                             "count_macs"))
def bitmask_spmm(x: jnp.ndarray, indices: jnp.ndarray, vals: jnp.ndarray,
                 *, bk: int = LANE, bn: int = LANE, bm: int = DEFAULT_BM,
                 sub_m: Optional[int] = None, two_sided: bool = False,
                 interpret: Optional[bool] = None,
                 count_macs: bool = False):
    """``x [M, K] @ W [K, N]`` with W in chunk-block-sparse layout.

    indices: int32 [n_blocks, max_nz] (k-chunk ids, -1 padded)
    vals:    [n_blocks, max_nz, bk, bn]
    ``sub_m`` (default: ``bm``) sets the row granularity of the two-sided
    activation skip. With ``count_macs`` also returns an int32 [nb, mb]
    map of executed sub-block MACs per grid cell.
    Returns [M, N] in x.dtype (fp32 accumulation).
    """
    interpret = resolve_interpret(interpret)
    M, K = x.shape
    nb, max_nz = indices.shape
    N = nb * bn
    sub_m = bm if sub_m is None else sub_m
    assert M % bm == 0 and K % bk == 0, (M, K, bm, bk)
    assert bm % sub_m == 0, (bm, sub_m)
    mb = M // bm

    # activation-side sub-block occupancy (two-sided mode); tiny O(MK) pass
    occ = flat_occupancy(x, sub_m, bk)

    grid = (nb, mb, max_nz)
    kernel = functools.partial(_kernel, nsteps=max_nz, two_sided=two_sided,
                               sub_m=sub_m, bm=bm, kb=K // bk,
                               count_macs=count_macs)
    out_shape = jax.ShapeDtypeStruct((M, N), x.dtype)
    out_specs = pl.BlockSpec((bm, bn), lambda n, m, j, idx, occ_: (m, n))
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if count_macs:
        cnt_shape, cnt_spec = count_output(nb, mb)
        out_shape = [out_shape, cnt_shape]
        out_specs = [out_specs, cnt_spec]
        scratch.append(pltpu.SMEM((1,), jnp.int32))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # indices, occupancy
            grid=grid,
            in_specs=[
                # x tile: row block m, K-chunk chosen by the prefetched index
                pl.BlockSpec((bm, bk),
                             lambda n, m, j, idx, occ_: (m, jnp.maximum(idx[n, j], 0))),
                # W tile for (n, j)
                pl.BlockSpec((1, 1, bk, bn), lambda n, m, j, idx, occ_: (n, j, 0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(indices, occ, x, vals)
    if count_macs:
        return out[0], count_rows(out[1])
    return out


def bitmask_spmm_wl(x: jnp.ndarray, vals: jnp.ndarray, wl: WorkList, *,
                    bk: int = LANE, bn: int = LANE,
                    bm_rows: int = DEFAULT_BM,
                    interpret: Optional[bool] = None,
                    executor: Optional[str] = None) -> jnp.ndarray:
    """Work-list-compacted ``x @ W``: the FFN-shaped frontend of
    :func:`repro.kernels.worklist_core.worklist_spmm`.

    Where :func:`bitmask_spmm` runs the dense ``(nb, mb, max_nz)`` grid
    and predicates dead tiles in-lane (``sub_m`` row sub-blocks inside a
    128-row block), this variant runs exactly ``wl.num_steps`` scheduled
    steps. Built at ``bm_rows = sub_m`` granularity, a single-live-lane
    decode batch schedules exactly its live (m-sub-block, k-chunk) pairs
    instead of predicating the full grid — the §3.2 telescoping applied
    to the FFN decode path. Bit-identical to :func:`bitmask_spmm` (tests
    pin it on both executors).
    """
    return worklist_spmm(x, vals, wl, bk=bk, bn=bn, bm_rows=bm_rows,
                         interpret=interpret, executor=executor)[0]
