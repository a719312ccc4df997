#!/usr/bin/env python3
"""XLA's dense conv on the same chip: the program's ``dense_forward``
(float32 at HIGHEST) over the configuration's pruned network at batch 8,
timed by the host clock over ``--seconds`` after one warm call. A
baseline read once, not a metric.

    python3 bench/dense_baseline.py --config vgg16_224 --seconds 5
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import harness
    import loadgen
    import spec
    import weights as W
    devs = harness.require_chips(1)
    harness.enable_caches()
    from repro.vision import dense_forward
    config = spec.load_json(os.path.join(spec.BENCH, "configs",
                                         args.config + ".json"))
    model = harness.build_model(config, W.make_weights(config, args.seed), 1)
    x = jnp.asarray(loadgen.blob_images(
        np.random.default_rng(args.seed), args.batch, config["input_size"],
        config["map_density"]))
    fwd = jax.jit(lambda x: dense_forward(model, x))
    fwd(x).block_until_ready()
    n, t0 = 0, time.monotonic()
    while time.monotonic() - t0 < args.seconds:
        fwd(x).block_until_ready()
        n += 1
    dt = time.monotonic() - t0
    print(json.dumps({"config": args.config, "batch": args.batch,
                      "calls": n, "seconds": dt,
                      "img_per_s": n * args.batch / dt,
                      "kind": devs[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
