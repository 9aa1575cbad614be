"""End-to-end behaviour tests for the TriMoE system."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core import simulate
from repro.core.simulator import SimFlags

REPO = Path(__file__).resolve().parents[1]


def test_paper_headline_claims_hold():
    """The core claim chain on the paper's flagship workload: TriMoE beats
    every baseline, predictor lands in band, overhead bounded."""
    cfg = get_config("deepseek-v2-236b")
    rs = {p: simulate(cfg, 512, policy=p, n_steps=4)
          for p in ("klotski", "enkt", "monde", "trimoe")}
    best = min(v.moe_time for k, v in rs.items() if k != "trimoe")
    speedup = best / rs["trimoe"].moe_time
    assert speedup > 1.5, speedup  # paper band: 2.12-2.83x
    r = rs["trimoe"]
    assert r.migration_overhead / r.step_time < 0.033
    assert r.migration_accuracy > 0.7


def test_train_loop_end_to_end(tmp_path):
    """launch/train.py trains, checkpoints, and auto-resumes."""
    from repro.launch.train import main

    args = [
        "--arch", "llama3.2-3b", "--smoke", "--steps", "20",
        "--batch", "4", "--seq", "32", "--lr", "2e-3",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "8", "--log-every", "50",
    ]
    losses = main(args)
    assert losses[-1] < losses[0]
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path)) == 16
    # resume: continues from step 16, runs only the remaining 4
    losses2 = main(args)
    assert len(losses2) == 4


def test_serve_loop_end_to_end(monkeypatch, tmp_path):
    """launch/serve.py decodes with the tiered runtime + migrations."""
    from repro.launch.serve import main

    # a set JAX_COMPILATION_CACHE_DIR leaves JAX's (already read) config
    # alone, so this run writes no compile cache into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    generated = main([
        "--arch", "granite-moe-1b-a400m", "--smoke",
        "--requests", "2", "--batch", "2",
        "--prompt-len", "8", "--new-tokens", "4",
    ])
    assert generated >= 8


def test_compile_cache_dir_is_env_or_fixed_checkout_dir(monkeypatch, tmp_path):
    """The environment's JAX_COMPILATION_CACHE_DIR wins with nothing set
    in code; without it the cache is <checkout>/.jax_cache, a path that
    does not change from run to run."""
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.serve import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_chip_smoke_refuses_to_run_off_tpu():
    """chip_smoke.py serves only on a TPU: on the CPU it exits nonzero
    before building anything and prints no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_checks_pass_at_smoke_scale(monkeypatch, capsys):
    """The chip smoke's serve-and-check path, rehearsed at a tiny size
    with the kernels in interpret mode, so a change that would break it
    on the chip breaks here first."""
    import dataclasses
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    for name, value in (("N_REQUESTS", 4), ("PROMPT_LENS", (64, 96)),
                        ("SHARED_PREFIX", 32), ("NEW_TOKENS", 8),
                        ("CACHE_LEN", 104)):
        monkeypatch.setattr(cs, name, value)
    cfg = dataclasses.replace(
        reduce_for_smoke(get_config(cs.ARCH)),
        paged_attn_backend="pallas", moe_backend="pallas",
    )
    cs.run(cfg, seed=0, kernel_backend="pallas", interpret=True)
    out = capsys.readouterr().out
    assert "FAILED" not in out
    assert "timed pass: requests=4 tokens=32" in out


def test_zigzag_batcher_lifecycle():
    from repro.serving.batching import Request, ZigzagBatcher

    b = ZigzagBatcher(4, n_groups=2)
    for rid in range(6):
        b.submit(Request(rid, np.arange(4, dtype=np.int32), max_new_tokens=2))
    served = 0
    for _ in range(20):
        nb = b.next_batch()
        if nb is None:
            continue
        live, toks = nb
        assert toks.shape == (len(live), 1)
        b.record(live, np.ones((len(live), 1), np.int32))
        served += len(live)
        if len(b.completed) == 6:
            break
    assert len(b.completed) == 6
    assert all(len(r.generated) == 2 for r in b.completed)


def test_watchdog_and_elastic_policy():
    from repro.distributed.fault_tolerance import ElasticPolicy, StepWatchdog

    wd = StepWatchdog(min_steps=5)
    for s in range(30):
        wd.observe(s, 1.0 + 0.01 * np.random.default_rng(s).random())
    assert not wd.flagged
    for s in range(30, 36):
        wd.observe(s, 10.0 if s % 2 else 1.0)
    assert wd.flagged
    pol = ElasticPolicy(max_flags_per_window=2, window=100)
    assert pol.should_reshard(wd, 36)


def test_compressed_psum_numerics():
    from repro.distributed.collectives import int8_dequantize, int8_quantize

    x = jnp.asarray(np.random.default_rng(0).standard_normal(1000), jnp.float32)
    q, scale = int8_quantize(x)
    err = np.abs(np.asarray(int8_dequantize(q, scale) - x))
    assert err.max() <= float(scale) / 2 + 1e-6
