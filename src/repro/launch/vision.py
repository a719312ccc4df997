"""Vision launcher: batched sparse CNN inference through the engine.

    PYTHONPATH=src python -m repro.launch.vision --bench VGGNet --smoke
    PYTHONPATH=src python -m repro.launch.vision --bench AlexNet \
        --image-size 35 --requests 6 --slots 2 --density 0.368
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.vision --bench VGGNet \
        --mesh 4 --slots 4 --requests 8

Builds a pruned network for one of the simulator's Table-1 benchmarks
(AlexNet / VGG16 / ResNet-18/50), serves staggered image requests through
the round-robin vision engine, verifies the first image against the dense
oracle, and prints per-layer measured densities + skipped-tile fractions.
``--smoke`` runs a tiny 2-layer net at 16 px (the CI step). ``--mesh N``
shards the engine's image batch over an N-device data mesh (bitwise
identical to solo; simulate devices on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before launch) and
prints the per-device schedule counters. Interpret-mode wall time is NOT
TPU performance; the structural numbers are what carries.
"""
from __future__ import annotations

import argparse
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.vision import (ImageRequest, VisionEngine, autotune_model,
                          build_vision_model, layer_table,
                          measured_densities, oracle_check)


def blob_images(rng: np.random.Generator, n: int, size: int,
                live_frac: float) -> np.ndarray:
    """Synthetic feature-map-sparse inputs: non-negative blobs on a zero
    background, ~``live_frac`` of the pixels live (the paper's ReLU
    feature-map sparsity, spatially clustered so tile skips are real)."""
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


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="VGGNet",
                    choices=["AlexNet", "VGGNet", "ResNet18", "ResNet50"])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny 2-layer net at 16 px (CI)")
    ap.add_argument("--layers", type=int, default=None,
                    help="truncate the network to N layers")
    ap.add_argument("--image-size", type=int, default=None)
    ap.add_argument("--density", type=float, default=None,
                    help="filter density (default: paper Table 1)")
    ap.add_argument("--pattern", default="unstructured",
                    choices=["unstructured", "chunk"],
                    help="pruning pattern: chunk = tile-aligned structured "
                         "pruning (real dead chunks for the schedule)")
    ap.add_argument("--autotune", action="store_true",
                    help="per-layer tile autotuning (deterministic cost "
                         "model); the engine bakes the tuned schedules")
    ap.add_argument("--map-density", type=float, default=None,
                    help="input live-pixel fraction (default: Table 1)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--stagger", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="data-shard the engine batch over an N-device "
                         "mesh (N must divide --slots; bitwise identical "
                         "to solo)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    mesh = None
    if args.mesh is not None:
        from repro.vision.mesh import data_mesh
        mesh = data_mesh(args.mesh)
    layers = 2 if args.smoke and args.layers is None else args.layers
    size = args.image_size if args.image_size is not None else \
        (16 if args.smoke else 32)
    model = build_vision_model(args.bench, density=args.density,
                               num_layers=layers, seed=args.seed,
                               pattern=args.pattern,
                               mesh_devices=args.mesh)
    if args.autotune:
        recs = autotune_model(model, size)
        for i, r in recs.items():
            c = r.config
            print(f"autotune layer {i}: bm={c.bm_rows} bn={c.bn} "
                  f"sub_m={c.sub_m} im2col={c.im2col}")
    from repro.core import simulator as S
    md = args.map_density if args.map_density is not None else \
        S.BENCHMARKS[args.bench].map_density
    rng = np.random.default_rng(args.seed)
    imgs = blob_images(rng, args.requests, size, md)

    # correctness: first image, sparse kernel path vs dense oracle
    x0 = jnp.asarray(imgs[:1])
    out0, stats, rel = oracle_check(model, x0)
    print(f"{args.bench}: {model.num_layers} layers @ {size}px, "
          f"filter density {model.density}")
    print(f"sparse conv path vs dense oracle: rel err {rel:.2e}")
    assert rel < 1e-4, "sparse conv path diverged from the dense oracle"

    for row in layer_table(stats):
        print(row)
    fd, md_meas = measured_densities(stats)
    print(f"measured network densities: filters {fd:.3f}, maps {md_meas:.3f}")

    eng = VisionEngine(model, num_slots=args.slots, use_tuned=args.autotune,
                       mesh=mesh)
    reqs = [ImageRequest(rid=i, image=imgs[i], arrival=i * args.stagger)
            for i in range(args.requests)]
    produced = eng.run(reqs)
    st = eng.stats
    print(f"engine: {st.images} images on {args.slots} slots in "
          f"{st.engine_steps} steps, {st.wall_s:.2f}s "
          f"({st.img_per_s:.2f} img/s steady, compile {st.compile_s:.2f}s, "
          f"util {st.slot_utilization:.2f})")
    if mesh is not None:
        sc = eng.schedule_counters()
        print(f"mesh: {sc['num_devices']} devices, per-device steps "
              f"{sc['per_device_steps']}, imbalance "
              f"{sc['step_imbalance']:.3f}, scaling efficiency "
              f"{sc['step_scaling_efficiency']:.3f}")
    assert np.allclose(produced[0], np.asarray(out0)[0], atol=1e-5), \
        "engine output must match the solo forward"
    print("engine output matches solo forward")


if __name__ == "__main__":
    main()
