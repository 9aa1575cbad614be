"""Kernel micro-benchmarks.

On this CPU container Pallas kernels run in interpret mode, so wall time
is NOT hardware-representative; these benches (a) time the jnp reference
path (the number that matters on CPU), (b) validate kernel-vs-oracle
numerics at bench shapes, and (c) report the analytic TPU-v5e roofline
time for each kernel's workload — the figure of merit the Pallas tiling
targets.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.hardware import TPU_V5E
from repro.kernels.expert_gemv import cold_expert_ffn
from repro.kernels.flash_attention import mha
from repro.kernels.moe_gemm import grouped_expert_ffn, grouped_expert_matmul
from repro.kernels.paged_attention import (
    paged_decode_gqa,
    paged_decode_gqa_ref,
    resolve_backend,
)


def _time(fn, *args, iters=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_moe_gemm():
    rng = np.random.default_rng(0)
    t, d, f, e = 256, 512, 512, 8
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    eo = jnp.asarray(rng.integers(0, e, t), jnp.int32)
    w = jnp.asarray(rng.standard_normal((e, d, f)) * 0.1, jnp.float32)
    us_ref = _time(
        lambda: grouped_expert_matmul(x, eo, w, capacity=t + e * 128, backend="ref")
    )
    got = grouped_expert_matmul(x, eo, w, capacity=t + e * 128, backend="pallas")
    ref = grouped_expert_matmul(x, eo, w, capacity=t + e * 128, backend="ref")
    err = float(jnp.max(jnp.abs(got - ref)))
    flops = 2 * t * d * f
    tpu_us = flops / TPU_V5E.flops * 1e6
    print(f"kernel/moe_gemm,{us_ref:.1f},err={err:.1e} tpu_roofline_us={tpu_us:.2f}")


def bench_moe_grouped_ffn():
    """The fused prefill expert FFN (gate+up wide GEMM, silu, down) the
    model's pallas moe_backend runs over dispatch buffers — einsum
    reference timed, kernel numerics validated at the bench shape."""
    rng = np.random.default_rng(4)
    e, c, d, f = 8, 128, 512, 1024
    h = jnp.asarray(rng.standard_normal((e, c, d)) * 0.5, jnp.float32)
    wg = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    wu = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    wd = jnp.asarray(rng.standard_normal((e, f, d)) * 0.05, jnp.float32)
    us_ref = _time(lambda: grouped_expert_ffn(h, wg, wu, wd, backend="ref"))
    got = grouped_expert_ffn(h, wg, wu, wd, backend="pallas")
    ref = grouped_expert_ffn(h, wg, wu, wd, backend="ref")
    err = float(jnp.max(jnp.abs(got - ref)))
    flops = 6 * e * c * d * f  # gate + up + down GEMMs
    tpu_us = flops / TPU_V5E.flops * 1e6
    print(f"kernel/moe_grouped_ffn,{us_ref:.1f},err={err:.1e} "
          f"tpu_roofline_us={tpu_us:.2f}")


def bench_expert_gemv():
    rng = np.random.default_rng(1)
    e, c, d, f = 8, 4, 512, 2048
    x = jnp.asarray(rng.standard_normal((e, c, d)) * 0.5, jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    w3 = jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((e, f, d)) * 0.05, jnp.float32)
    us_ref = _time(lambda: cold_expert_ffn(x, w1, w3, w2, backend="ref"))
    got = cold_expert_ffn(x, w1, w3, w2, backend="pallas")
    ref = cold_expert_ffn(x, w1, w3, w2, backend="ref")
    err = float(jnp.max(jnp.abs(got - ref)))
    bytes_ = e * 3 * d * f * 4
    tpu_us = bytes_ / TPU_V5E.hbm_bw * 1e6  # cold experts are BW-bound
    print(f"kernel/expert_gemv,{us_ref:.1f},err={err:.1e} tpu_bw_bound_us={tpu_us:.2f}")


def bench_flash_attention():
    rng = np.random.default_rng(2)
    b, s, h, dh = 1, 512, 4, 64
    q = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    us_ref = _time(lambda: mha(q, k, v, causal=True, backend="ref"))
    got = mha(q, k, v, causal=True, bq=128, bk=128, backend="pallas")
    ref = mha(q, k, v, causal=True, backend="ref")
    err = float(jnp.max(jnp.abs(got - ref)))
    flops = 4 * b * h * s * s * dh / 2  # causal halves
    tpu_us = flops / TPU_V5E.flops * 1e6
    print(f"kernel/flash_attention,{us_ref:.1f},err={err:.1e} tpu_roofline_us={tpu_us:.2f}")


def bench_paged_attention():
    """Paged decode attention: dense gather over the FULL block-table
    width (the pre-kernel serving path) vs the block-sparse active-width
    walk (what the engine slices to + what the Pallas kernel does per
    row). Rows are short relative to the slot capacity — the
    long-context serving shape the kernel exists for."""
    try:
        from benchmarks._paged_bench import build_case, time_full_vs_sparse
    except ImportError:  # script mode: benchmarks/ itself is on sys.path
        from _paged_bench import build_case, time_full_vs_sparse

    rng = np.random.default_rng(3)
    b, kv, g, hd, bs, nb = 4, 4, 1, 64, 16, 64  # 1024-token slots
    q, pool_k, pool_v, tables, pos = build_case(
        rng, b=b, kv=kv, g=g, hd=hd, bs=bs, nb=nb,
        pos=[37, 91, 13, 55],  # rows ~4-9% full
    )
    us_full, us_sparse, w = time_full_vs_sparse(q, pool_k, pool_v, tables, pos)
    got = paged_decode_gqa(q, pool_k, pool_v, tables[:, :w], pos,
                           interpret=resolve_backend("pallas").interpret)
    ref = paged_decode_gqa_ref(q, pool_k, pool_v, tables[:, :w], pos)
    err = float(jnp.max(jnp.abs(got - ref)))
    # the dense path moves nb/w x the K/V bytes per step
    bytes_full = 2 * b * nb * bs * kv * hd * 4
    bytes_sparse = 2 * b * w * bs * kv * hd * 4
    tpu_full = bytes_full / TPU_V5E.hbm_bw * 1e6  # decode attn is BW-bound
    tpu_sparse = bytes_sparse / TPU_V5E.hbm_bw * 1e6
    print(f"kernel/paged_attention,{us_sparse:.1f},err={err:.1e} "
          f"dense_gather_us={us_full:.1f} speedup={us_full / us_sparse:.2f}x "
          f"active_blocks={w}/{nb} "
          f"tpu_bw_bound_us={tpu_sparse:.2f} (dense {tpu_full:.2f})")


def bench_scheduler_latency():
    """The online scheduler must cost << one decode step (paper §4.2)."""
    from repro.core.cost_model import CostModel, ExpertShape
    from repro.core.scheduler import ExpertPlacement, MakespanScheduler
    from repro.core.cost_model import LOCALIZED, STRIPED

    cm = CostModel()
    sched = MakespanScheduler(cm, ExpertShape(5120, 1536))
    rng = np.random.default_rng(0)
    loads = rng.zipf(1.5, 160).clip(0, 512).astype(float)
    pls = [
        ExpertPlacement(LOCALIZED if i % 3 else STRIPED, i % 16, gpu_cached=i < 4)
        for i in range(160)
    ]
    t0 = time.perf_counter()
    n = 20
    for _ in range(n):
        sched.schedule(loads, pls)
    us = (time.perf_counter() - t0) / n * 1e6
    print(f"scheduler/layer_schedule,{us:.0f},experts=160 (must be << decode step ~10ms)")


def run_all():
    bench_moe_gemm()
    bench_moe_grouped_ffn()
    bench_expert_gemv()
    bench_flash_attention()
    bench_paged_attention()
    bench_scheduler_latency()
