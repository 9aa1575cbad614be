"""End-to-end serving driver: continuous-batching TriMoE serving loop.

Runs the full online system at example scale: queued requests with
staggered prompt lengths are admitted into decode slots (per-request
prefill through the tiered MoE runtime), zigzag groups decode at
per-slot positions, and expert migrations replan in the gaps between
group steps.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-1b-a400m \
      --smoke --requests 8 --batch 4 --groups 2 --new-tokens 16
"""
from __future__ import annotations

import argparse
import os
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, reduce_for_smoke
from repro.models.model import init_params
from repro.serving.batching import Request
from repro.serving.loop import ServingLoop


# <checkout>/.jax_cache — git-ignored, and fixed: the cache directory
# is part of what a later run must match to find an entry again
_REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. A `JAX_COMPILATION_CACHE_DIR` from the environment is
    JAX's own setting and wins untouched; otherwise the cache lives at
    the fixed `<checkout>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE_DIR))
    return str(_REPO_CACHE_DIR)


def build_loop(cfg, *, batch: int, groups: int, cache_len: int,
               cold_capacity_frac: float = 1.0, seed: int = 0,
               bucket_table="auto", max_admit_wait: int = 4) -> ServingLoop:
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return ServingLoop(
        cfg, params,
        batch_size=batch, n_groups=groups, cache_len=cache_len,
        cold_capacity_frac=cold_capacity_frac,
        bucket_table=bucket_table, max_admit_wait=max_admit_wait,
    )


def make_requests(cfg, n: int, prompt_len: int, new_tokens: int,
                  stagger: int = 0, seed: int = 0):
    """n requests; with `stagger`, prompt lengths cycle over the
    inclusive range [prompt_len, prompt_len + stagger]."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = prompt_len + (rid % (stagger + 1))
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=new_tokens,
        ))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--stagger", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--no-buckets", action="store_true",
                    help="legacy exact-length prefill (one jit compile per "
                         "distinct prompt length) instead of the default "
                         "length-bucketed masked prefill")
    ap.add_argument("--max-admit-wait", type=int, default=4,
                    help="admit a partial same-bucket cohort after this many "
                         "admission rounds (starvation cap)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)
    assert cfg.moe is not None, "serve.py drives the TriMoE MoE path"

    cache_len = args.prompt_len + args.stagger + args.new_tokens
    loop = build_loop(cfg, batch=args.batch, groups=args.groups,
                      cache_len=cache_len,
                      bucket_table=None if args.no_buckets else "auto",
                      max_admit_wait=args.max_admit_wait)
    for r in make_requests(cfg, args.requests, args.prompt_len,
                           args.new_tokens, stagger=args.stagger):
        loop.submit(r)

    done = loop.run()
    eng = loop.engine
    buckets = (list(loop.bucket_table.widths)
               if loop.bucket_table is not None else "off")
    print(f"[serve] {loop.stats.summary()}")
    print(f"[serve] migrations={eng.stats.migrations} plans={eng.stats.plans} "
          f"prefills={eng.stats.prefills} "
          f"predictor_acc={eng.predictor.stats.accuracy:.2f}")
    print(f"[serve] buckets={buckets} prefill_compiles={eng.prefill_compiles}")
    for r in done[: min(4, len(done))]:
        print(f"[serve]   rid={r.rid} prompt_len={r.prompt_len} "
              f"tokens={r.generated[:8]}{'...' if len(r.generated) > 8 else ''}")
    return loop.stats.generated_tokens


if __name__ == "__main__":
    main()
