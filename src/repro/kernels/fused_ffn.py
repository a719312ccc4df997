"""Fused gated-FFN Pallas kernel: in-proj -> activation -> gate-mul in ONE
``pallas_call``.

``SparseFFN.__call__`` used to be three kernel launches (w_in, w_gate,
w_out) with the activation applied between them in XLA — every launch
round-trips the [M, F] hidden tensor through HBM. GrateTile/Phantom both
show the packing/dispatch glue, not the MAC core, is where sparse designs
lose their wins; this kernel keeps the fp32 accumulators for the in- and
gate-projections resident in VMEM, applies the nonlinearity and the gate
multiply at the flush, and emits the *activated* hidden tensor directly.
The output projection stays a second :func:`bitmask_spmm` launch where the
activation sparsity (squared-ReLU zeros) feeds the two-sided skip.

Both matmuls share the chunk-block-sparse weight layout and the row
sub-block activation occupancy of :mod:`repro.kernels.bitmask_spmm`
(``subblock_macs`` is imported from there, so the skip predicate is the
same circuit in both kernels).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitmask_spmm import flat_occupancy, subblock_macs
from repro.kernels.worklist_core import (  # noqa: F401  (re-exports)
    ACTS, DEFAULT_BM, GATED_ACTS, LANE, WorkList, activation_occupancy,
    resolve_interpret, worklist_spmm)
from repro.kernels.worklist_core import activate as _activate


def _kernel(*args, nsteps: int, act: str, two_sided: bool, sub_m: int,
            bm: int, kb: int, gated: bool):
    if gated:
        (in_idx_ref, g_idx_ref, occ_ref, x_in_ref, w_in_ref, x_g_ref,
         w_g_ref, o_ref, acc_h_ref, acc_g_ref) = args
    else:
        in_idx_ref, occ_ref, x_in_ref, w_in_ref, o_ref, acc_h_ref = args
        acc_g_ref = None
    n_i = pl.program_id(0)
    m_i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_h_ref[...] = jnp.zeros_like(acc_h_ref)
        if acc_g_ref is not None:
            acc_g_ref[...] = jnp.zeros_like(acc_g_ref)

    k_in = in_idx_ref[n_i, j]
    subblock_macs(k_in >= 0, jnp.maximum(k_in, 0), occ_ref, m_i, x_in_ref,
                  w_in_ref[0, 0], acc_h_ref, None, two_sided=two_sided,
                  sub_m=sub_m, bm=bm, kb=kb)
    if gated:
        k_g = g_idx_ref[n_i, j]
        subblock_macs(k_g >= 0, jnp.maximum(k_g, 0), occ_ref, m_i, x_g_ref,
                      w_g_ref[0, 0], acc_g_ref, None, two_sided=two_sided,
                      sub_m=sub_m, bm=bm, kb=kb)

    @pl.when(j == nsteps - 1)
    def _flush():
        g = acc_g_ref[...] if acc_g_ref is not None else None
        o_ref[...] = _activate(acc_h_ref[...], g, act).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("act", "bk", "bn", "bm",
                                             "sub_m", "two_sided",
                                             "interpret"))
def fused_ffn_spmm(x: jnp.ndarray, in_idx: jnp.ndarray, in_vals: jnp.ndarray,
                   gate_idx: Optional[jnp.ndarray] = None,
                   gate_vals: Optional[jnp.ndarray] = None, *, act: str,
                   bk: int = LANE, bn: int = LANE, bm: int = DEFAULT_BM,
                   sub_m: Optional[int] = None, two_sided: bool = True,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """``act(x @ W_in [, x @ W_gate])`` with both weights chunk-block-sparse.

    x [M, K]; in_idx/gate_idx int32 [nb, max_nz]; in_vals/gate_vals
    [nb, max_nz, bk, bn]. Gated acts (swiglu/geglu) require the gate
    operands; for the rest they must be None. Returns the *activated*
    hidden [M, nb*bn] in x.dtype (both projections accumulate in fp32 and
    the activation is applied to the fp32 accumulators).
    """
    interpret = resolve_interpret(interpret)
    assert act in ACTS, act
    gated = act in GATED_ACTS
    assert (gate_idx is not None) == gated, (act, gate_idx is None)
    M, K = x.shape
    nb, mnz_in = in_idx.shape
    sub_m = bm if sub_m is None else sub_m
    assert M % bm == 0 and K % bk == 0, (M, K, bm, bk)
    assert bm % sub_m == 0, (bm, sub_m)
    mb = M // bm

    occ = flat_occupancy(x, sub_m, bk)

    if gated:
        # align the two chunk lists on one j axis (pad with -1 / zero tiles)
        mnz = max(mnz_in, gate_idx.shape[1])

        def pad_idx(i):
            return jnp.pad(i, ((0, 0), (0, mnz - i.shape[1])),
                           constant_values=-1)

        def pad_vals(v):
            return jnp.pad(v, ((0, 0), (0, mnz - v.shape[1]), (0, 0), (0, 0)))

        in_idx, gate_idx = pad_idx(in_idx), pad_idx(gate_idx)
        in_vals, gate_vals = pad_vals(in_vals), pad_vals(gate_vals)
    else:
        mnz = mnz_in

    grid = (nb, mb, mnz)
    kernel = functools.partial(_kernel, nsteps=mnz, act=act,
                               two_sided=two_sided, sub_m=sub_m, bm=bm,
                               kb=K // bk, gated=gated)
    x_spec_in = pl.BlockSpec(
        (bm, bk), (lambda n, m, j, i_idx, g_idx, occ_:
                   (m, jnp.maximum(i_idx[n, j], 0))) if gated else
        (lambda n, m, j, i_idx, occ_: (m, jnp.maximum(i_idx[n, j], 0))))
    w_spec_in = pl.BlockSpec(
        (1, 1, bk, bn), (lambda n, m, j, i_idx, g_idx, occ_:
                         (n, j, 0, 0)) if gated else
        (lambda n, m, j, i_idx, occ_: (n, j, 0, 0)))
    if gated:
        in_specs = [
            x_spec_in, w_spec_in,
            pl.BlockSpec((bm, bk), lambda n, m, j, i_idx, g_idx, occ_:
                         (m, jnp.maximum(g_idx[n, j], 0))),
            pl.BlockSpec((1, 1, bk, bn),
                         lambda n, m, j, i_idx, g_idx, occ_: (n, j, 0, 0)),
        ]
        out_specs = pl.BlockSpec(
            (bm, bn), lambda n, m, j, i_idx, g_idx, occ_: (m, n))
        scalars = (in_idx, gate_idx, occ)
        operands = (x, in_vals, x, gate_vals)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32),
                   pltpu.VMEM((bm, bn), jnp.float32)]
    else:
        in_specs = [x_spec_in, w_spec_in]
        out_specs = pl.BlockSpec((bm, bn),
                                 lambda n, m, j, i_idx, occ_: (m, n))
        scalars = (in_idx, occ)
        operands = (x, in_vals)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((M, nb * bn), x.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(*scalars, *operands)


def fused_ffn_spmm_wl(x: jnp.ndarray, in_vals: jnp.ndarray, wl: WorkList,
                      gate_vals: Optional[jnp.ndarray] = None, *, act: str,
                      bk: int = LANE, bn: int = LANE,
                      bm_rows: int = DEFAULT_BM,
                      interpret: Optional[bool] = None,
                      executor: Optional[str] = None) -> jnp.ndarray:
    """Work-list-compacted fused FFN: ``act(x @ W_in [, x @ W_gate])``.

    ``wl`` is the compacted schedule from
    :func:`repro.kernels.worklist_core.build_worklist` — for gated acts a
    *two-stream* list (``gate_indices`` at build time) whose slots are the
    union of the in- and gate-projection live sets, each stream MACing in
    its own ascending-j order so the fp32 accumulation order (and hence
    the bits) matches the predicated :func:`fused_ffn_spmm` exactly.
    Built at ``bm_rows = sub_m`` granularity the schedule holds exactly
    the live (m-sub-block, k-chunk) pairs — the decode-path telescoping.
    """
    assert act in ACTS, act
    gated = act in GATED_ACTS
    assert (gate_vals is not None) == gated, (act, gate_vals is None)
    return worklist_spmm(x, in_vals, wl, vals2=gate_vals, bk=bk, bn=bn,
                         bm_rows=bm_rows, act=act, interpret=interpret,
                         executor=executor)[0]
