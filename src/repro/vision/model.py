"""Full-network sparse CNN forward for the simulator's Table-1 benchmarks.

The cycle simulator (:mod:`repro.core.simulator`) has carried the paper's
benchmark topologies as :class:`LayerSpec` lists since the seed; this module
turns those specs into *runnable* networks: synthetic He-initialized
filters, magnitude-pruned to the paper's densities, offline-processed by the
conv-aware packing chain (:mod:`repro.sparsity.conv`), and executed layer by
layer through the implicit-GEMM two-sided Pallas kernel with fused ReLU and
in-kernel occupancy emission (:mod:`repro.kernels.sparse_conv`).

The nets are fully convolutional, so any input size runs; pooling placement
is derived *statically* from the spec list (a max-pool wherever the paper's
layer table halves the spatial size), which keeps measured per-layer
densities attributable to the paper's layers. Inception-v4's branchy
topology does not linearize into a chain and stays simulator-only.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import simulator as S
from repro.core.sparse import Padding, Stride
from repro.kernels.bitmask_spmm import DEFAULT_BM
from repro.kernels.sparse_conv import conv_out_size, sparse_conv2d_nhwc
from repro.sparsity.conv import PackedConv, build_sparse_chain

# stem geometry per arch: (canonical input size, layer-0 stride, padding)
ARCH_STEM: Dict[str, Tuple[int, Tuple[int, int], str]] = {
    "AlexNet": (227, (4, 4), "VALID"),
    "VGGNet": (224, (1, 1), "SAME"),
    "ResNet18": (224, (2, 2), "SAME"),
    "ResNet50": (224, (2, 2), "SAME"),
}
SUPPORTED_ARCHS = tuple(ARCH_STEM)


@dataclasses.dataclass
class VisionLayer:
    conv: PackedConv
    stride: Tuple[int, int]
    padding: Padding
    pool_after: Optional[Tuple[int, int]]  # (window, stride) max-pool or None


@dataclasses.dataclass
class VisionModel:
    name: str
    layers: List[VisionLayer]
    input_size: int
    density: float                # pruning target (paper Table 1 filters)
    _fwd_cache: Dict = dataclasses.field(default_factory=dict, repr=False,
                                         compare=False)

    @property
    def num_layers(self) -> int:
        return len(self.layers)


def _pool_between(prev_oh: int, next_oh: int) -> Optional[Tuple[int, int]]:
    """Max-pool (window, stride) mapping the spec's spatial step, if any."""
    if next_oh >= prev_oh:
        return None
    for k, s in ((2, 2), (3, 2), (2, 3), (3, 3)):
        if (prev_oh - k) // s + 1 == next_oh:
            return (k, s)
    raise ValueError(f"no pool maps {prev_oh} -> {next_oh}")


def build_vision_model(name: str = "VGGNet", *,
                       density: Optional[float] = None, seed: int = 0,
                       num_layers: Optional[int] = None,
                       balance_filters: bool = True,
                       num_shards: int = 16,
                       pattern: str = "unstructured",
                       mesh_devices: Optional[int] = None) -> VisionModel:
    """Synthetic pruned network for one simulator benchmark.

    ``density`` defaults to the paper's Table-1 filter density for the
    benchmark; ``num_layers`` truncates the chain (smoke nets). Weights are
    He-scaled so activations stay O(1) through deep chains. ``pattern``
    selects the pruner (:func:`repro.sparsity.conv.build_sparse_chain`):
    ``"chunk"`` prunes at tile granularity in the tap-major layout, so the
    packed chunk maps carry real dead chunks for the schedule to skip.
    ``mesh_devices`` additionally runs the pack-time cluster balance
    (greedy output-chunk-group assignment, paper Section 4 round-robin) so
    each layer's work lists carry a per-device shard map.
    """
    if name not in ARCH_STEM:
        raise ValueError(f"{name} does not linearize into a conv chain; "
                         f"supported: {SUPPORTED_ARCHS}")
    if num_layers is not None and num_layers < 1:
        raise ValueError(f"num_layers must be >= 1, got {num_layers}")
    bench = S.BENCHMARKS[name]
    specs = list(bench.layers)
    if num_layers is not None:
        specs = specs[:num_layers]
    for a, b in zip(specs, specs[1:]):
        assert a.n == b.d, f"{name} chain break: {a} -> {b}"
    density = bench.filter_density if density is None else density
    rng = np.random.default_rng(seed)
    weights = []
    for spec in specs:
        fan_in = spec.k * spec.k * spec.d
        weights.append((rng.normal(size=(spec.k, spec.k, spec.d, spec.n))
                        * np.sqrt(2.0 / fan_in)).astype(np.float32))
    chain = build_sparse_chain(weights, density=density,
                               num_shards=num_shards,
                               balance_filters=balance_filters,
                               pattern=pattern, mesh_devices=mesh_devices)
    stem_size, stem_stride, stem_pad = ARCH_STEM[name]
    layers: List[VisionLayer] = []
    for i, (spec, conv) in enumerate(zip(specs, chain)):
        stride: Stride = stem_stride if i == 0 else (1, 1)
        padding: Padding = stem_pad if i == 0 else "SAME"
        pool = (_pool_between(spec.oh, specs[i + 1].oh)
                if i + 1 < len(specs) else None)
        layers.append(VisionLayer(conv, stride, padding, pool))
    return VisionModel(name, layers, stem_size, density)


def route_bucket(buckets: Tuple[int, ...], h: int, w: int) -> int:
    """Canonical shape for an [h, w] image: the smallest bucket that holds
    it (zero-pad up — never upsize past the next canonical shape), or the
    largest bucket when the image exceeds every one (downscale).

    The GrateTile framing: a small set of canonical shapes bounds the
    compile count while the padding cost per image stays below one bucket
    step.
    """
    if not buckets:
        raise ValueError("need at least one shape bucket")
    side = max(h, w)
    for b in sorted(buckets):
        if side <= b:
            return b
    return max(buckets)


def fit_image(image: np.ndarray, size: int) -> np.ndarray:
    """Canonicalize one [H, W, C] image to [size, size, C].

    Images at or under the bucket are zero-padded bottom/right — content
    is preserved *exactly* (padded pixels are dead and the two-sided skip
    elides their row blocks), which is what keeps batched outputs bitwise
    comparable to per-request runs. Oversized images are area-resampled
    down (lossy — only taken past the largest bucket).
    """
    img = np.asarray(image, np.float32)
    if img.ndim != 3:
        raise ValueError(f"image must be [H, W, C], got {img.shape}")
    h, w, c = img.shape
    if h <= size and w <= size:
        return np.pad(img, ((0, size - h), (0, size - w), (0, 0)))
    out = jax.image.resize(jnp.asarray(img), (size, size, c), "linear")
    return np.asarray(out, np.float32)


def layer_geometry(model: VisionModel, input_size: int, *,
                   bm_rows: int = DEFAULT_BM,
                   use_tuned: bool = False) -> List[Dict[str, int]]:
    """Static per-layer geometry walk for one input size (host arithmetic
    only — no trace, no kernel). Mirrors :func:`_forward_layers` exactly:
    conv output size per layer spec, row padding to whole ``bm_rows``
    blocks, and the pool placement rule of :func:`max_pool` (skipped when
    the map is smaller than the window). Returns one dict per layer with
    ``oh/ow/m_img/m_pad/bm_rows/mb_per_img`` — what serving layers need
    to attribute cached work lists to shape buckets and to build
    cross-request fetch plans without compiling."""
    out: List[Dict[str, int]] = []
    h = w = input_size
    for layer in model.layers:
        c = layer.conv
        cfg = c.tuned.config if (use_tuned and c.tuned is not None) else None
        bm = cfg.bm_rows if cfg else bm_rows
        oh, ow = conv_out_size(h, w, c.kh, c.kw, layer.stride, layer.padding)
        oh, ow = int(oh), int(ow)
        m_img = oh * ow
        m_pad = m_img + (-m_img) % bm
        out.append({"oh": oh, "ow": ow, "m_img": m_img, "m_pad": m_pad,
                    "bm_rows": bm, "mb_per_img": m_pad // bm})
        h, w = oh, ow
        if layer.pool_after is not None and min(h, w) >= layer.pool_after[0]:
            win, s = layer.pool_after
            h = (h - win) // s + 1
            w = (w - win) // s + 1
    return out


def max_pool(x: jnp.ndarray, window: int, stride: int) -> jnp.ndarray:
    """Channel-wise max-pool (skipped when the map is already too small)."""
    if min(x.shape[1], x.shape[2]) < window:
        return x
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def _forward_layers(model: VisionModel, x: jnp.ndarray, *, sub_m: int,
                    two_sided: bool, schedule: str, executor: Optional[str],
                    im2col: str, interpret: Optional[bool],
                    use_tuned: bool = False) -> jnp.ndarray:
    """The pure whole-net graph: every layer (patch extraction included)
    in one trace, activations handed layer-to-layer in-device.

    ``use_tuned`` applies each layer's cached autotune winner
    (``conv.tuned``, from :func:`repro.kernels.autotune.autotune_model`) —
    per-layer ``bm_rows`` / ``sub_m`` / im2col strategy instead of the
    global knobs; layers without a record keep the globals.

    Each layer runs under the name scope ``layer<NN>``, its parts under
    ``im2col``, ``walker`` and ``pool`` (op metadata only: the compiled
    program and its instruction names are unchanged), so a device trace
    attributes every operation to its layer."""
    for i, layer in enumerate(model.layers):
        c = layer.conv
        cfg = c.tuned.config if (use_tuned and c.tuned is not None) else None
        with jax.named_scope(f"layer{i:02d}"):
            x, _ = sparse_conv2d_nhwc(
                x, c.packed, c.kh, c.kw, c.cout, stride=layer.stride,
                padding=layer.padding,
                sub_m=cfg.sub_m if cfg else sub_m,
                bm_rows=cfg.bm_rows if cfg else DEFAULT_BM,
                im2col=cfg.im2col if cfg else im2col,
                two_sided=two_sided,
                fuse_relu=True, interpret=interpret, schedule=schedule,
                executor=executor, layout=c.layout, wl_cache=c.wl_cache)
            if layer.pool_after is not None:
                with jax.named_scope("pool"):
                    x = max_pool(x, *layer.pool_after)
    return x


def compile_forward(model: VisionModel, *, sub_m: int = 8,
                    two_sided: bool = True, schedule: str = "compact",
                    executor: Optional[str] = None, im2col: str = "auto",
                    interpret: Optional[bool] = None,
                    donate: bool = False,
                    use_tuned: bool = False,
                    mesh=None) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """One jit of the full forward (cached on the model per config).

    The layer loop is unrolled over the static layer specs inside a single
    ``jax.jit``: im2col patch extraction, the work-list kernels, and the
    pools all fuse into one compiled program — no host boundary between
    layers, and the telescoped work lists are baked in at trace time from
    the pack-time chunk lists. ``use_tuned`` bakes each layer's cached
    autotune config (the per-layer tile shapes and im2col strategy) into
    the trace; the cache key includes those configs, so re-tuning a layer
    gets a fresh compile instead of a stale hit. ``donate=True`` donates
    the input buffer (serving engines hand a fresh batch every step);
    leave it off when the caller reuses ``x``. Retracing per input shape
    is handled by jit.

    ``mesh`` data-shards the forward: the batch dim splits over the
    mesh's data axes (``B`` must divide by the data extent) and every
    device runs the full per-image work-list walk on its local slice
    under ``shard_map`` — no cross-device collective in the graph, so
    the sharded output is bitwise equal to the single-device pipeline.
    """
    tuned_key = tuple(
        l.conv.tuned.config.key()
        if (use_tuned and l.conv.tuned is not None) else None
        for l in model.layers)
    mesh_key = None if mesh is None else (
        tuple(mesh.axis_names), tuple(d.id for d in mesh.devices.flat))
    key = (sub_m, two_sided, schedule, executor, im2col, interpret, donate,
           use_tuned, tuned_key, mesh_key)
    fn = model._fwd_cache.get(key)
    if fn is None:
        def vision_forward(x):       # the compiled module's name
            return _forward_layers(
                model, x, sub_m=sub_m, two_sided=two_sided,
                schedule=schedule, executor=executor, im2col=im2col,
                interpret=interpret, use_tuned=use_tuned)
        if mesh is not None:
            from repro.vision.mesh import shard_forward
            fn = shard_forward(vision_forward, mesh, donate=donate)
        else:
            fn = jax.jit(vision_forward,
                         donate_argnums=(0,) if donate else ())
        model._fwd_cache[key] = fn
    return fn


def forward(model: VisionModel, x: jnp.ndarray, *, sub_m: int = 8,
            two_sided: bool = True, interpret: Optional[bool] = None,
            collect_stats: bool = False, schedule: str = "compact",
            executor: Optional[str] = None, im2col: str = "auto",
            compiled: Optional[bool] = None, use_tuned: bool = False
            ) -> Tuple[jnp.ndarray, List[Dict[str, float]]]:
    """Whole network through the sparse conv kernel path.

    x: [B, H, W, 3] float32. By default (``compiled=None``) the fast path
    runs: one jit of the full forward over the telescoped work-list
    schedule (see :func:`compile_forward`). ``collect_stats`` switches to
    the instrumented per-layer path and returns one dict per layer with
    the measured densities the simulator feedback loop consumes: scalar
    map/filter densities (the paper's Table-1 quantities), chunk-granular
    weight density, the kernel's executed vs skippable tile MACs (from
    its own ``count_macs`` counters — the skip numbers are the kernel's,
    not a model's), and the compacted schedule's step counts (scheduled
    vs dense-grid, with the §3.2 request-combining model applied to the
    layer's work list).
    """
    if compiled is None:
        compiled = not collect_stats
    if compiled and not collect_stats:
        fn = compile_forward(model, sub_m=sub_m, two_sided=two_sided,
                             schedule=schedule, executor=executor,
                             im2col=im2col, interpret=interpret,
                             use_tuned=use_tuned)
        return fn(x), []
    stats: List[Dict[str, float]] = []
    for i, layer in enumerate(model.layers):
        c = layer.conv
        if collect_stats:
            map_scalar = float(jnp.mean((x != 0).astype(jnp.float32)))
        out, aux = sparse_conv2d_nhwc(
            x, c.packed, c.kh, c.kw, c.cout, stride=layer.stride,
            padding=layer.padding, sub_m=sub_m, two_sided=two_sided,
            fuse_relu=True, emit_occupancy=collect_stats,
            interpret=interpret, count_macs=collect_stats,
            schedule="dense" if collect_stats else schedule,
            executor=executor, im2col=im2col, layout=c.layout,
            wl_cache=c.wl_cache,
            compact_activations=collect_stats,
            report_schedule=collect_stats)
        if collect_stats:
            executed = float(np.asarray(aux["mac_counts"]).sum())
            n_chunks = int(np.asarray(c.packed.indices >= 0).sum())
            # denominators at the kernel's own (padded) tiling, in the same
            # unit the counters use: sub-block MACs when two-sided, whole
            # tiles when one-sided (subblock_macs counts once per tile then)
            mb_total = int(aux["mac_counts"].shape[1])
            units = mb_total * (DEFAULT_BM // sub_m) if two_sided else mb_total
            kb = c.packed.shape[0] // c.packed.bk
            weight_tile = n_chunks * units
            dense_tile = c.packed.n_blocks * kb * units
            occ = np.asarray(aux["occupancy"])
            spec = S.BENCHMARKS[model.name].layers[i]
            sched = aux["schedule"]
            stats.append({
                "scheduled_steps": sched["scheduled_steps"],
                "live_chunk_steps": sched["live_chunk_steps"],
                "flush_only_steps": sched["flush_only_steps"],
                "dense_grid_steps": sched["dense_grid_steps"],
                "static_scheduled_steps": sched["static_scheduled_steps"],
                "schedule_requests": sched["combining"]["requests"],
                "schedule_fetches": sched["combining"]["fetches"],
                "combine_factor": sched["combining"]["combine_factor"],
                "layer": i,
                "kh": c.kh, "cin": c.cin, "cout": c.cout,
                "macs": float(x.shape[0]) * aux["oh"] * aux["ow"]
                        * c.kh * c.kw * c.cin * c.cout,
                "map_scalar_density": map_scalar,
                "filter_scalar_density": c.scalar_density(),
                "filter_chunk_density": c.chunk_density(),
                "dead_chunk_fraction": c.dead_chunk_fraction(),
                "layout": c.layout,
                "pattern": c.pattern,
                "paper_map_density": S.BENCHMARKS[model.name].map_density,
                "paper_filter_density": S.BENCHMARKS[model.name]
                                         .filter_density,
                "executed_tile_macs": executed,
                "weight_tile_macs": float(weight_tile),
                "dense_tile_macs": float(dense_tile),
                "skipped_tile_frac": 1.0 - executed / max(weight_tile, 1),
                "out_occupancy_density": float(occ.mean()),
                "spec_oh": spec.oh,
            })
        x = out
        if layer.pool_after is not None:
            x = max_pool(x, *layer.pool_after)
    return x, stats


def dense_forward(model: VisionModel, x: jnp.ndarray) -> jnp.ndarray:
    """Oracle: the same pruned (chain-folded) filters through
    ``jax.lax.conv_general_dilated`` + ReLU + pooling, at ``HIGHEST``
    precision so it is a float32 reference on every backend (the TPU's
    default conv precision is a reduced-precision MXU pass)."""
    for layer in model.layers:
        w = jnp.asarray(layer.conv.w_dense)
        x = jax.lax.conv_general_dilated(
            x, w, layer.stride, layer.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        x = jnp.maximum(x, 0.0)
        if layer.pool_after is not None:
            x = max_pool(x, *layer.pool_after)
    return x


def oracle_check(model: VisionModel, x: jnp.ndarray, *, sub_m: int = 8,
                 two_sided: bool = True, collect_stats: bool = True
                 ) -> Tuple[jnp.ndarray, List[Dict[str, float]], float]:
    """Sparse kernel path vs dense oracle on one batch.

    Returns ``(sparse_out, stats, rel_err)`` — the shared verification step
    every entry point (launcher, example, bench) runs before reporting.
    """
    out, stats = forward(model, x, sub_m=sub_m, two_sided=two_sided,
                         collect_stats=collect_stats)
    ref = dense_forward(model, x)
    rel = float(jnp.abs(out - ref).max()) / (float(jnp.abs(ref).max()) + 1e-9)
    return out, stats, rel


def layer_table(stats: List[Dict[str, float]],
                with_paper: bool = False) -> List[str]:
    """Formatted per-layer density/skip rows (one shared schema for all
    entry points)."""
    hdr = (f"  {'layer':>5s} {'shape':>17s} {'map':>6s} {'filter':>7s} "
           f"{'w-chunk':>8s} {'skipped':>8s}")
    if with_paper:
        hdr += f" {'map(paper)':>11s} {'filt(paper)':>12s}"
    rows = [hdr]
    for s in stats:
        row = (f"  {s['layer']:5d} {s['kh']}x{s['kh']}x{s['cin']:4d}"
               f"->{s['cout']:4d}  {s['map_scalar_density']:6.3f} "
               f"{s['filter_scalar_density']:7.3f} "
               f"{s['filter_chunk_density']:8.3f} "
               f"{s['skipped_tile_frac']:8.3f}")
        if with_paper:
            row += (f" {s['paper_map_density']:11.3f} "
                    f"{s['paper_filter_density']:12.3f}")
        rows.append(row)
    return rows


def schedule_summary(stats: List[Dict[str, float]]) -> Dict[str, float]:
    """Network totals of the telescoped-schedule counters: what the
    compacted grid schedules vs what the dense grid would have, plus the
    §3.2 request-combining factor over the whole net."""
    tot = {k: float(sum(s[k] for s in stats)) for k in
           ("scheduled_steps", "live_chunk_steps", "flush_only_steps",
            "dense_grid_steps", "static_scheduled_steps",
            "schedule_requests", "schedule_fetches")}
    tot["combine_factor"] = (tot["schedule_requests"]
                             / max(tot["schedule_fetches"], 1e-9))
    tot["grid_compaction"] = (1.0 - tot["scheduled_steps"]
                              / max(tot["dense_grid_steps"], 1e-9))
    return tot


def measured_densities(stats: List[Dict[str, float]]
                       ) -> Tuple[float, float]:
    """MAC-weighted network filter / map scalar densities — the Table-1
    quantities, measured from the tensors the kernel actually ran."""
    macs = np.array([s["macs"] for s in stats])
    fd = float((macs * [s["filter_scalar_density"] for s in stats]).sum()
               / macs.sum())
    md = float((macs * [s["map_scalar_density"] for s in stats]).sum()
               / macs.sum())
    return fd, md
