"""Serve granite-moe-1b-a400m at its published widths on one TPU chip.

    python chip_smoke.py [--seed 0]

The quickest proof that the serving system starts on the chip. One
process builds the full 24-layer model from a seed (`get_config` +
`init_params` through `repro.launch.serve.build_loop`) and serves
through `ServingLoop` with the library defaults: paged KV, radix prefix
cache, chunked piggyback prefill, "auto" kernel backends and the tier
scheduler replanning. A warm-up pass compiles every shape; a second
pass of new requests is timed. Then it checks:

  * JAX runs on a TPU: anything else exits nonzero before serving;
  * every `kernel.*` span resolved to the Pallas kernel, compiled
    (interpret=False);
  * every request completed with its `max_new_tokens` tokens;
  * one paged prefill plus one decode step give last-position logits
    that agree between the Pallas kernels and the jnp references,
    within LOGITS_RTOL, on a full-width fp32 copy of the model cut to
    CHECK_LAYERS layers (see there for why).

Earlier lines print facts of the run (device, tier split, compiles,
tokens, wall time, logits difference); they are not benchmark metrics.
The last line is one JSON object naming the device, printed only when
every check passed.
"""
from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ARCH = "granite-moe-1b-a400m"
BATCH, GROUPS = 8, 2
N_REQUESTS = 16
# Prompt lengths are multiples of the loop's 32-token piggyback chunk,
# and the shared prefix is too, so every chunk has width 32: prefill
# compiles once per table width and no more.
PROMPT_LENS = (256, 288)
SHARED_PREFIX = 128  # tokens every even-rid prompt starts with
NEW_TOKENS = 32
CACHE_LEN = max(PROMPT_LENS) + NEW_TOKENS
# The logits check runs a full-width copy of the model cut to
# CHECK_LAYERS layers, in fp32 with fp32 matmuls on both paths. In bf16
# the two paths round at different points and a near-tied router top-k
# can flip, which compounds with depth: on a 24-layer bf16 model the
# backends diverge whatever the kernels do. In fp32 they differ by
# summation order and online-vs-exact softmax only (~1e-6 relative),
# while a wrong block, head, mask or expert moves the logits by 1e-2 or
# more. The bound sits between: ||pallas - ref|| / ||ref|| <= 1e-3.
CHECK_LAYERS = 2
LOGITS_RTOL = 1e-3
LOG = "[chip_smoke]"


def fail(msg: str) -> None:
    raise SystemExit(f"{LOG} FAILED: {msg}")


def make_requests(cfg, rng, system, rid0: int):
    """N_REQUESTS prompts drawn from `rng`; even rids start with the
    shared `system` prefix, so later admissions hit the radix cache."""
    from repro.serving.batching import Request

    reqs = []
    for i in range(N_REQUESTS):
        plen = PROMPT_LENS[(i // 2) % len(PROMPT_LENS)]
        head = system if i % 2 == 0 else np.zeros((0,), np.int32)
        tail = rng.integers(0, cfg.vocab_size, plen - len(head))
        reqs.append(Request(
            rid=rid0 + i,
            prompt=np.concatenate([head, tail]).astype(np.int32),
            max_new_tokens=NEW_TOKENS,
        ))
    return reqs


def serve_pass(loop, reqs):
    """Serve `reqs` to completion; check every one got its tokens."""
    for r in reqs:
        loop.submit(copy.deepcopy(r))
    seen = len(loop.completions)
    done = loop.run()[seen:]
    if sorted(r.rid for r in done) != sorted(r.rid for r in reqs):
        fail(f"{len(done)}/{len(reqs)} requests completed")
    vocab = loop.cfg.vocab_size
    for r in done:
        toks = np.asarray(r.generated)
        if len(toks) != r.max_new_tokens or not ((toks >= 0) & (toks < vocab)).all():
            fail(f"request {r.rid}: {len(toks)}/{r.max_new_tokens} tokens "
                 f"{toks[:8].tolist()}...")
    return done


def check_kernel_spans(events, interpret: bool) -> dict:
    """Every `kernel.*` span must have resolved to Pallas with the
    given interpret flag; the three kernels of the serving path (paged
    attention, which decode runs at a chunk of one, and the two expert
    FFNs) must all have been staged. Returns {op: spans}."""
    seen: dict = {}
    for ev in events:
        if not ev.get("name", "").startswith("kernel."):
            continue
        args = ev.get("args", {})
        if args.get("backend") != "pallas" or args.get("interpret") != interpret:
            fail(f"{ev['name']} resolved to {args}, want pallas "
                 f"interpret={interpret}")
        seen[ev["name"]] = seen.get(ev["name"], 0) + 1
    want = {"kernel.paged_prefill_gqa", "kernel.grouped_expert_ffn",
            "kernel.cold_expert_ffn"}
    if not want <= set(seen):
        fail(f"kernels never staged: {sorted(want - set(seen))}")
    return seen


def backend_logits(cfg, params, tiered, sizes, backend: str, prompts, lens,
                   toks) -> np.ndarray:
    """Last-position logits of one paged prefill (all rows in one call)
    and one decode step, through a fresh engine whose two kernel
    backends are `backend`. Engines share `params` and `tiered`, so only
    the kernels differ."""
    from repro.serving.engine import TriMoEServingEngine
    from repro.serving.paged_kv import PagedKVCache

    cfg = dataclasses.replace(cfg, paged_attn_backend=backend,
                              moe_backend=backend)
    rows = len(lens)
    kv = PagedKVCache(cfg, rows, CACHE_LEN, prefix_cache=False)
    eng = TriMoEServingEngine(cfg, params, kv, tiered, sizes=sizes,
                              prefill_rows=rows)
    slots = list(range(rows))
    for i in slots:
        kv.admit_slot(i, prompts[i, :lens[i]])
    first = eng.prefill_slots_paged(prompts, slots, lens, np.zeros(rows, np.int32))
    for i in slots:
        kv.ensure_block(i, int(lens[i]))
    nxt, _ = eng.step_slots_paged(toks, lens, slots, kv.table_rows(slots))
    return np.concatenate([np.asarray(first, np.float32),
                           np.asarray(nxt, np.float32)])


def check_logits(loop, seed: int, kernel_backend: str) -> float:
    """Pallas-vs-reference relative L2 difference of the logits of the
    served model's widths and tier split, cut to CHECK_LAYERS in fp32."""
    import jax

    from repro.models.model import init_params
    from repro.serving.engine import fill_tiers_from_params, init_tiered_for_model

    cfg = dataclasses.replace(loop.cfg, n_layers=CHECK_LAYERS,
                              param_dtype="float32", obs=None)
    sizes = loop.engine.sizes
    k_params, k_tiers = jax.random.split(jax.random.PRNGKey(seed + 1))
    params = init_params(k_params, cfg)
    tiered = fill_tiers_from_params(
        params, init_tiered_for_model(k_tiers, cfg, sizes), cfg)
    rng = np.random.default_rng(seed + 1)
    lens = np.asarray([32, 29, 17, 8], np.int32)
    prompts = np.zeros((len(lens), int(lens.max())), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.integers(0, cfg.vocab_size, n)
    toks = rng.integers(0, cfg.vocab_size, (len(lens), 1)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = backend_logits(cfg, params, tiered, sizes, kernel_backend,
                             prompts, lens, toks)
        ref = backend_logits(cfg, params, tiered, sizes, "ref",
                             prompts, lens, toks)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        fail("non-finite logits")
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    print(f"{LOG} logits pallas vs ref ({CHECK_LAYERS} layers fp32, "
          f"{got.shape[0]} rows x {cfg.vocab_size}): rel_l2={rel!r} "
          f"max_abs={float(np.abs(got - ref).max())!r} "
          f"max_abs_ref={float(np.abs(ref).max())!r} "
          f"(bound rel_l2 <= {LOGITS_RTOL})")
    if rel > LOGITS_RTOL:
        fail(f"pallas vs ref logits rel_l2 {rel} > {LOGITS_RTOL}")
    return rel


@contextlib.contextmanager
def counting_compiles():
    """Count backend compiles (loads from the persistent cache
    included), their seconds, and persistent-cache hits while open."""
    import jax

    counts = {"n": 0, "s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["n"] += 1
            counts["s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield counts
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def run(cfg, *, seed: int, kernel_backend: str = "auto",
        interpret: bool = False) -> None:
    """Serve, check and report. `cfg` carries the kernel backends the
    serving loop resolves; `interpret` is what its spans must show."""
    with counting_compiles() as compiles:
        _serve_and_check(cfg, seed, kernel_backend, interpret, compiles)


def _serve_and_check(cfg, seed, kernel_backend, interpret, compiles):
    from repro.launch.serve import build_loop
    from repro.obs import ObsConfig

    cfg = dataclasses.replace(cfg, obs=ObsConfig(trace=True))
    loop = build_loop(cfg, batch=BATCH, groups=GROUPS, cache_len=CACHE_LEN,
                      seed=seed)
    eng = loop.engine
    print(f"{LOG} model {cfg.name}: {cfg.n_layers} layers d_model={cfg.d_model} "
          f"experts={cfg.moe.n_experts} top_k={cfg.moe.top_k} "
          f"d_expert={cfg.moe.d_expert} param_dtype={cfg.param_dtype}")
    print(f"{LOG} tiers hot/warm/cold={tuple(eng.sizes)} "
          f"backends paged_attn={tuple(eng.paged_attn_backend)} "
          f"moe={tuple(eng.moe_backend)}")

    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, SHARED_PREFIX).astype(np.int32)
    serve_pass(loop, make_requests(cfg, rng, system, rid0=0))
    spans = check_kernel_spans(loop.obs.tracer.events, interpret)
    print(f"{LOG} kernel spans (all pallas, interpret={interpret}): {spans}")
    print(f"{LOG} warm-up: compiles={compiles['n']} "
          f"compile_s={compiles['s']!r} cache_hits={compiles['cache_hits']}")

    loop.stats.reset()
    n_before, hits_before = compiles["n"], loop.kv.stats.hit_tokens
    done = serve_pass(loop, make_requests(cfg, rng, system, rid0=N_REQUESTS))
    st = loop.stats
    print(f"{LOG} timed pass: requests={len(done)} "
          f"tokens={st.generated_tokens} wall_s={st.wall_s!r} "
          f"compiles_in_pass={compiles['n'] - n_before} "
          f"decode_steps={st.decode_steps} prefill_chunks={st.prefill_chunks} "
          f"replans={st.replans} migrations={st.migrations} "
          f"prefix_hit_tokens={loop.kv.stats.hit_tokens - hits_before}")

    check_logits(loop, seed, kernel_backend)
    print(f"{LOG} total: compiles={compiles['n']} "
          f"compile_s={compiles['s']!r} cache_hits={compiles['cache_hits']}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the prompts")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        fail(f"JAX found no TPU (backend {jax.default_backend()!r}); "
             f"this smoke never runs elsewhere")
    from repro.configs import get_config
    from repro.launch.serve import enable_compile_cache

    cache = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    print(f"{LOG} device {dev.platform} {dev.device_kind!r} x{len(devs)} "
          f"jax={jax.__version__} compile_cache={cache}")
    run(get_config(ARCH), seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
    }}))


if __name__ == "__main__":
    main()
