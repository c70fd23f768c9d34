"""Serving launcher — M2Cache engine or ZeRO-Inference baseline.

Real tiny model:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-14b --tiny \
      --gen-len 16 --batch 2

Paper-scale analytic mode (LLaMA geometry, modeled clock):
  PYTHONPATH=src python -m repro.launch.serve --paper-model llama-13b \
      --mode zero_infinity --gen-len 32
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import PAPER_MODELS, M2CacheEngine
from repro.configs.base import get_config
from repro.launch.jax_cache import setup_compile_cache
from repro.models import transformer as T


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--paper-model", default=None,
                    choices=list(PAPER_MODELS) + [None])
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--mode", default="m2cache",
                    choices=["m2cache", "zero_infinity"])
    ap.add_argument("--hbm-policy", default="atu",
                    choices=["atu", "lru", "none"])
    ap.add_argument("--no-ssd", action="store_true")
    ap.add_argument("--dram-gb", type=float, default=4.0)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.paper_model:
        eng = M2CacheEngine(paper_model=args.paper_model, mode=args.mode,
                            hbm_policy=args.hbm_policy,
                            use_ssd=not args.no_ssd,
                            dram_capacity_gb=args.dram_gb, seed=args.seed)
        res = eng.generate(gen_len=args.gen_len)
    else:
        cfg = get_config(args.arch, tiny=args.tiny)
        key = jax.random.PRNGKey(args.seed)
        params = T.init_params(key, cfg, dtype=jnp.float32, m2=True)
        eng = M2CacheEngine(cfg=cfg, params=params, mode=args.mode,
                            hbm_policy=args.hbm_policy,
                            use_ssd=not args.no_ssd,
                            dram_capacity_gb=args.dram_gb, seed=args.seed)
        prompts = np.asarray(jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size))
        res = eng.generate(prompts, gen_len=args.gen_len)

    print(json.dumps({
        "tokens_per_s_modeled": res.tokens_per_s,
        "modeled_s": res.modeled_s,
        "wall_s": res.wall_s,
        "cache": res.cache_stats,
        "carbon_g": res.carbon,
    }, indent=1, default=float))


if __name__ == "__main__":
    main()
