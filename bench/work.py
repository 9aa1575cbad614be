"""Operations and bytes the served tokens need, from the configuration's
widths alone (never from a kernel's padded grid or block size), so that
a change of layout or tiling is judged on the same work.

All counts are per layer kind and per token; a multiply-add is 2 FLOPs.
`cfg` is the configuration file's dict (bench/configs/<name>.json).
"""
from __future__ import annotations


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def is_moe_layer(cfg: dict, i: int) -> bool:
    pattern = cfg["moe"]["layer_pattern"]
    if pattern == "all":
        return True
    if pattern == "all_but_first":
        return i > 0
    if pattern == "every_2":
        return i % 2 == 1
    raise ValueError(f"unknown layer_pattern {pattern!r}")


def matmul_flops_per_token(cfg: dict) -> float:
    """Weight-matmul FLOPs of one token through every layer and the
    output head: attention projections, the dense FFN or the router,
    the top_k routed experts and the shared experts. Embedding lookup
    is a gather and counts nothing."""
    d, hd = cfg["d_model"], head_dim(cfg)
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    mo = cfg["moe"]
    attn = 2 * d * (h * hd + 2 * kv * hd) + 2 * h * hd * d
    expert = 2 * 3 * d * mo["d_expert"]
    total = 0.0
    for i in range(cfg["n_layers"]):
        total += attn
        if is_moe_layer(cfg, i):
            total += 2 * d * mo["n_experts"]  # router
            total += (mo["top_k"] + mo["n_shared"]) * expert
        elif cfg["d_ff"] > 0:
            total += 2 * 3 * d * cfg["d_ff"]
    return total + 2 * d * cfg["vocab_size"]


def attn_flops(cfg: dict, ctx: float) -> float:
    """Score and value FLOPs of one query attending `ctx` keys, in one
    layer: QK^T and PV are 2*ctx*hd each per head."""
    return 4.0 * ctx * cfg["n_heads"] * head_dim(cfg)


def attn_kv_bytes(cfg: dict, ctx: float, dtype_bytes: int = 2) -> float:
    """Bytes of K and V a row must read to attend `ctx` keys, in one
    layer: each key's K and V across the kv heads, read once per row."""
    return 2.0 * ctx * cfg["n_kv_heads"] * head_dim(cfg) * dtype_bytes


def decode_attn_work(cfg: dict, ctx: int):
    """(flops, bytes) of one decoded token at context `ctx` (keys
    attended, itself included), summed over the attention layers."""
    n = cfg["n_layers"]
    return n * attn_flops(cfg, ctx), n * attn_kv_bytes(cfg, ctx)


def prefill_attn_work(cfg: dict, past: int, length: int):
    """(flops, bytes) of one prefill chunk row: `length` queries at
    positions past..past+length-1, query i attending past+i+1 keys; the
    row reads its past+length keys once. Summed over attention layers."""
    n = cfg["n_layers"]
    keys = length * past + length * (length + 1) / 2.0
    return n * attn_flops(cfg, keys), n * attn_kv_bytes(cfg, past + length)


def roofline_s(flops: float, nbytes: float, peak: dict):
    """Least time the chip could take for the work, and which bound
    sets it ("flops" or "bytes")."""
    t_f = flops / peak["bf16_flops"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
