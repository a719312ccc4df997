"""Mesh-sharded sparse vision runtime (BARISTA clusters -> jax devices).

The paper scales two-sided sparsity to 32K MACs by splitting the array
into clusters that round-robin filter chunks and snarf operands off the
shared bus (Sections 3.2 and 4). The reproduction's analog maps
clusters onto a jax device mesh twice over:

* **data axis** — whole images shard across devices
  (:func:`data_mesh` + ``compile_forward(mesh=...)``): per-image work
  lists are device-local, so every device walks its own telescoped
  schedule and the sharded output is *bitwise* equal to the
  single-device pipeline (per-(n, m)-pair ascending-``j`` accumulation
  never crosses images).
* **model axis** — one layer's packed filter chunks shard by output
  chunk group (:func:`cout_sharded_spmm`): the pack-time greedy balance
  (``sparsity.conv.mesh_shard_assignment``) assigns row blocks so
  per-device scheduled-step counts balance within
  ``SHARD_BALANCE_TOL``; each device walks its padded schedule stream
  and the column slabs ride the :func:`ring_allgather` ppermute ring
  with the next layer's activation-occupancy bitmask piggybacked —
  communication for step ``s + 1`` overlaps the walk of step ``s``.

Everything here is also runnable on a 1-device mesh, where it
degenerates to the plain pipeline.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.collective_matmul import (exchange_overlap_fraction,
                                          ring_allgather)
from repro.dist.partitioning import dp_axes, image_batch_spec
from repro.kernels.worklist_core import (WorkList, per_shard_steps,
                                         shard_imbalance,
                                         shard_scaling_efficiency,
                                         shard_worklist_args,
                                         worklist_spmm_padded)


def data_mesh(num_devices: Optional[int] = None) -> Mesh:
    """1-D ``("data",)`` mesh over the first ``num_devices`` local devices.

    ``None`` takes every visible device. The CPU path reaches multiple
    devices via ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (set before importing jax — see tests/test_dist_vision.py).
    """
    devs = jax.devices()
    n = len(devs) if num_devices is None else int(num_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(f"num_devices={n} not in [1, {len(devs)}]")
    return Mesh(np.array(devs[:n]), ("data",))


def shard_forward(body, mesh: Mesh, *, donate: bool = False):
    """Jit of ``body`` (the whole-net layer walk) data-sharded over ``mesh``.

    ``body`` must be the pure [B, H, W, C] -> [B, oh, ow, cout] forward;
    the batch dim shards over the data axes (``B`` must divide by the
    data extent — shard_map enforces it at call time) and each device
    runs the full per-image work-list walk on its local slice. No
    cross-device collective appears in the data-parallel graph, which is
    why the sharded output is bitwise identical to the solo pipeline.
    """
    spec = image_batch_spec(mesh)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,), out_specs=spec,
                       check_vma=False)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def cout_sharded_spmm(patches: jnp.ndarray, vals: np.ndarray, wl: WorkList,
                      mesh: Mesh, *, bk: int, bn: int, bm_rows: int,
                      axis: str = "model",
                      occupancy: bool = False):
    """One cout-sharded layer under ``shard_map``: per-device padded
    schedule walk + overlapped ring exchange of the output slabs.

    ``wl`` must carry a contiguous equal-count ``shard_of`` (the
    pack-time cluster assignment, post shard permutation). Each device
    walks only its own row blocks' schedule stream
    (:func:`worklist_spmm_padded`), then the [M, nb_local * bn] column
    slabs ride the ppermute ring back to full width — with the next
    layer's activation-occupancy bitmask riding each hop when
    ``occupancy`` is set. Returns the full [M, N] output (every rank),
    bitwise equal to ``worklist_spmm(..., executor="xla")``.
    """
    if wl.shard_of is None:
        raise ValueError("worklist has no shard_of — pack with mesh_devices")
    d = int(mesh.shape[axis])
    args = shard_worklist_args(wl, d)
    nbl = wl.nb // d
    vals = np.asarray(vals)
    # [D, nb_local, max_nz, bk, bn] — each rank keeps only its row blocks
    vals_stack = vals.reshape(d, nbl, *vals.shape[1:])
    arrs = {k: jnp.asarray(v) for k, v in args.items()}
    mb = wl.mb

    def local(vals_d, n_d, m_d, k_d, j_d, valid_d):
        slab = worklist_spmm_padded(
            patches, vals_d[0], n_d[0], m_d[0], k_d[0], j_d[0], valid_d[0],
            bk=bk, bn=bn, bm_rows=bm_rows, nb_local=nbl, mb=mb)
        occ = None
        if occupancy:
            # next layer's activation-occupancy bitmask for this slab's
            # row blocks (one bit per [bm_rows, bn] tile), piggybacked on
            # the same ring hops the slab rides
            t = slab.reshape(-1, bm_rows, nbl, bn)
            occ = (jnp.abs(t).max(axis=(1, 3)) > 0).astype(jnp.int32)
        full, focc = ring_allgather(slab, axis, d, occupancy=occ, axis=-1)
        # every rank ends with the full tensors; keep the leading device
        # dim so out_specs can mention the mesh axis (check_rep=False
        # requires it) — the caller reads rank 0's copy
        if occupancy:
            return full[None], focc[None]
        return (full[None],)

    sharded = P(axis)
    out_specs = (sharded, sharded) if occupancy else (sharded,)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(sharded,) * 6, out_specs=out_specs, check_vma=False)
    res = fn(jnp.asarray(vals_stack), arrs["n"], arrs["m"],
             arrs["k"], arrs["j"], arrs["valid"])
    if occupancy:
        return res[0][0], res[1][0]
    return res[0][0]


def mesh_schedule_counters(model, num_devices: int) -> Dict[str, object]:
    """Aggregate per-device schedule accounting across a model's cached
    work lists — the observable §4 round-robin balance.

    Sums per-device scheduled-step counts over every layer whose packed
    chunks carry a cluster assignment (layers without one count as
    device-0 load, the honest accounting for an unsharded layer) and
    reports the committed balance metrics plus the modeled
    exchange-overlap fraction of the occupancy ring.
    """
    per_dev = np.zeros(num_devices, np.int64)
    layers = 0
    for layer in model.layers:
        for wl in layer.conv.wl_cache.values():
            if wl.shard_of is not None:
                per_dev += per_shard_steps(wl, num_shards=num_devices)
            else:
                per_dev[0] += wl.num_steps
            layers += 1
    walk = int(per_dev.max(initial=0))
    return {
        "num_devices": int(num_devices),
        "worklists": layers,
        "per_device_steps": [int(c) for c in per_dev],
        "step_imbalance": shard_imbalance(per_dev),
        "step_scaling_efficiency": shard_scaling_efficiency(per_dev),
        "exchange_overlap_fraction": exchange_overlap_fraction(
            walk, num_devices),
    }
