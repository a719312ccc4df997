#!/usr/bin/env python3
"""Chip smoke test: sparse VGG16 image serving at 224 px on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip data mesh, and only that

One chip: builds VGG16 (all 13 convs, Table-1 filter density, seed 0),
serves 8 blob images through ``VisionServer`` (4 slots, one 224 px bucket,
wall clock), checks every answer against the dense float32 reference
(``dense_forward`` at HIGHEST precision) on the same chip, checks that the
compiled forward holds one Mosaic kernel (``tpu_custom_call``) per conv
layer, then runs the vision launcher (``repro.launch.vision``) at 224 px,
whose instrumented oracle check drives the predicated kernels with MAC
counters and occupancy emission.

``--chips 4``: serves the same images through ``VisionServer`` on a 4-device
data mesh (8 slots), checks that the batch is spread over all four devices,
and compares the answers bitwise with the single-device compiled forward on
device 0.

Exits non-zero, printing no result, unless JAX's first device is a TPU.
Any failed check raises. The last line of standard output is the JSON
result with the device as JAX reports it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "VGGNet"
SIZE = 224
SEED = 0
IMAGES = 8
REL_TOL = 1e-4        # the vision launcher's oracle bound


def require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def make_images(n: int, size: int):
    import numpy as np
    from repro.core import simulator as S
    from repro.launch.vision import blob_images
    return blob_images(np.random.default_rng(SEED), n, size,
                       S.BENCHMARKS[ARCH].map_density)


def serve(model, imgs, *, num_slots: int, mesh=None):
    """Serve ``imgs`` through a wall-clock VisionServer; returns the answers
    stacked in request order."""
    import numpy as np
    from repro.serve.vision import VisionServer, WallClock
    from repro.vision import ImageRequest
    server = VisionServer(model, num_slots=num_slots, buckets=(SIZE,),
                          clock=WallClock(), mesh=mesh)
    produced = server.run([ImageRequest(rid=i, image=img)
                           for i, img in enumerate(imgs)])
    st = server.stats
    print(f"serve: {st.images} images on {num_slots} slots in "
          f"{st.engine_steps} steps; compile_s {st.compile_s} "
          f"wall_s {st.wall_s}")
    require(sorted(produced) == list(range(len(imgs))),
            f"served {sorted(produced)}, expected {len(imgs)} answers")
    out = np.stack([produced[i] for i in range(len(imgs))])
    require(bool(np.isfinite(out).all()), "non-finite answers")
    return out


def check_reference(model, imgs, out) -> None:
    """Every answer against the HIGHEST-precision dense reference."""
    import jax.numpy as jnp
    import numpy as np
    from repro.vision import dense_forward
    ref = np.asarray(dense_forward(model, jnp.asarray(imgs)))
    require(ref.shape == out.shape, f"shape {out.shape} != {ref.shape}")
    for i in range(len(imgs)):
        err = float(np.abs(out[i] - ref[i]).max())
        rel = err / (float(np.abs(ref[i]).max()) + 1e-9)
        print(f"image {i}: max abs err {err} / max |ref| = rel {rel}")
        require(rel < REL_TOL, f"image {i}: rel err {rel} >= {REL_TOL}")


def mosaic_kernels(fwd, shape) -> int:
    """``tpu_custom_call`` ops in the compiled program of ``fwd``."""
    import jax
    import jax.numpy as jnp
    hlo = fwd.lower(jax.ShapeDtypeStruct(shape, jnp.float32)).compile() \
        .as_text()
    return hlo.count('custom_call_target="tpu_custom_call"')


def peak_bytes(devices) -> str:
    stats = [d.memory_stats() or {} for d in devices]
    return ", ".join(f"{d.id}: {s.get('peak_bytes_in_use', 'not reported')}"
                     for d, s in zip(devices, stats))


def one_chip() -> None:
    import jax
    from repro.launch import vision as launch_vision
    from repro.vision import build_vision_model, compile_forward
    t0 = time.monotonic()
    model = build_vision_model(ARCH, seed=SEED)
    imgs = make_images(IMAGES, SIZE)
    print(f"{ARCH}: {model.num_layers} conv layers at {SIZE} px, filter "
          f"density {model.density}; host build_s {time.monotonic() - t0}")

    out = serve(model, imgs, num_slots=4)
    check_reference(model, imgs, out)

    # the server's own cached jit (same compile_forward key)
    kernels = mosaic_kernels(compile_forward(model, donate=True),
                             (4, SIZE, SIZE, 3))
    print(f"compiled forward: {kernels} tpu_custom_call ops for "
          f"{model.num_layers} conv layers")
    require(kernels == model.num_layers,
            "expected one Mosaic work-list kernel per conv layer")

    t1 = time.monotonic()
    launch_vision.main(["--bench", ARCH, "--image-size", str(SIZE)])
    print(f"launcher: wall_s {time.monotonic() - t1}")
    print(f"peak bytes in use: {peak_bytes(jax.devices()[:1])}")


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.dist.partitioning import image_batch_spec
    from repro.vision import build_vision_model, compile_forward, data_mesh
    devices = jax.devices()
    require(len(devices) >= 4, f"--chips 4 needs 4 devices, "
                               f"found {len(devices)}")
    mesh = data_mesh(4)
    model = build_vision_model(ARCH, seed=SEED)
    imgs = make_images(IMAGES, SIZE)
    print(f"{ARCH}: {model.num_layers} conv layers at {SIZE} px on a "
          f"{mesh.devices.size}-device data mesh")

    out = serve(model, imgs, num_slots=8, mesh=mesh)

    # the server's sharded jit: one image pair per device, nothing pinned
    # to device 0
    fwd = compile_forward(model, donate=True, mesh=mesh)
    y = fwd(jax.device_put(jnp.asarray(imgs),
                           NamedSharding(mesh, image_batch_spec(mesh))))
    shards = sorted((s.device.id, s.data.shape[0])
                    for s in y.addressable_shards)
    print(f"output shards (device id, images): {shards}")
    require(len({d for d, _ in shards}) == 4
            and all(n == IMAGES // 4 for _, n in shards),
            "the mesh forward did not spread the batch over 4 devices")
    kernels = mosaic_kernels(fwd, (8, SIZE, SIZE, 3))
    print(f"sharded forward: {kernels} tpu_custom_call ops per device")
    require(kernels == model.num_layers,
            "expected one Mosaic work-list kernel per conv layer")

    solo = compile_forward(model)
    ref = np.asarray(solo(jax.device_put(jnp.asarray(imgs), devices[0])))
    for name, got in (("served", out), ("sharded forward", np.asarray(y))):
        diff = int((got != ref).sum())
        print(f"{name} vs single-device forward on device 0: "
              f"{diff} differing elements of {ref.size}")
        require(diff == 0, f"{name} answers are not bitwise equal")
    print(f"peak bytes in use: {peak_bytes(devices[:4])}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the data-mesh path and its check")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (first device is {dev.platform}); "
              "nothing run", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {dev.device_kind}, {len(jax.devices())} visible; "
          f"compile cache {enable_compile_cache()}")
    t0 = time.monotonic()
    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    print(f"total wall_s {time.monotonic() - t0}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
