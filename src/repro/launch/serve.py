"""Serving launcher: batched generation or continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_3b --smoke \
        [--batch B] [--prompt-len P] [--new-tokens N]
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --smoke \
        --continuous [--requests R] [--slots S] [--stagger K]

Default mode prefills a synthetic prompt batch in one pass and decodes;
``--continuous`` drives the barrier-free scheduler instead (staggered
request arrivals, per-slot positions, slot reuse). ``--sparse`` runs the
BARISTA inference mode: ``sparsify_model`` prunes/balances/packs every
eligible FFN offline and the engine decodes through the two-sided
chunk-sparse kernels (skipped-tile stats are probed mid-run). Full configs
require TPU hardware; on this host use --smoke (the dry-run proves the
full-config serve_step compiles on the production mesh).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import load_config, load_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve import Request, Scheduler, generate
from repro.sparsity.sparse_ffn import sparsify_model


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="serve staggered requests via the scheduler")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--stagger", type=int, default=2)
    ap.add_argument("--sparse", action="store_true",
                    help="serve through the two-sided sparse FFN kernels")
    ap.add_argument("--density", type=float, default=0.35,
                    help="pruning density for --sparse")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = load_smoke(args.arch) if args.smoke else load_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = M.init_params(key, cfg)
    if args.sparse:
        cfg = dataclasses.replace(cfg, sparse_ffn=True)
        params = sparsify_model(params, cfg, density=args.density,
                                num_shards=4)

    if args.continuous:
        rng = np.random.default_rng(args.seed)
        prompts = rng.integers(1, cfg.vocab,
                               (args.requests, args.prompt_len)).astype(np.int32)
        reqs = [Request(rid=i, prompt=prompts[i], max_new=args.new_tokens,
                        arrival=i * args.stagger)
                for i in range(args.requests)]
        sch = Scheduler(cfg, params, num_slots=args.slots,
                        max_len=args.prompt_len + args.new_tokens)
        produced = sch.run(reqs, probe_ffn=args.sparse)
        sparse_stats = sch.ffn_probe
        st = sch.stats
        print(f"arch={cfg.name} continuous: {args.requests} requests on "
              f"{args.slots} slots, {st.tokens} tokens in {st.wall_s:.2f}s "
              f"({st.tok_per_s:.1f} tok/s incl. compile, "
              f"util {st.slot_utilization:.2f})")
        if sparse_stats is not None:
            print(f"sparse FFN: weight-tile density "
                  f"{sparse_stats['weight_tile_macs'] / sparse_stats['dense_tile_macs']:.2f}, "
                  f"activation-side skipped {sparse_stats['skipped_frac']:.2f}, "
                  f"executed {sparse_stats['executed_frac']:.3f} of dense tile MACs")
        print("sample:", produced[0][:24])
        return

    prompt = jax.random.randint(jax.random.fold_in(key, 1),
                                (args.batch, args.prompt_len), 1, cfg.vocab,
                                dtype=jnp.int32)
    src = None
    if cfg.encoder_layers:
        src = 0.02 * jax.random.normal(
            jax.random.fold_in(key, 2),
            (args.batch, args.prompt_len, cfg.d_model))

    t0 = time.time()
    out = generate(params, cfg, prompt, args.new_tokens, src_embeds=src)
    out.block_until_ready()
    dt = time.time() - t0
    toks = args.batch * args.new_tokens
    print(f"arch={cfg.name} generated {out.shape} in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. compile)")
    print("sample:", out[0, :24].tolist())


if __name__ == "__main__":
    main()
