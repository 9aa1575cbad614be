"""Seeded weights, made by the benchmark and not by the program.

Every leaf is a pure function of (seed, layer, leaf id): the same seed
gives the same weights on any device. `program_weights` lays them out as
the program's `ServingLoop` takes them (params pytree + tier buffers)
in ONE jitted call on the device, drawing each routed expert straight
into its initial tier slot, so no flat expert stack is ever made. The
plain reference (bench/references/<architecture>.py) calls the same leaf functions again
after the program's state is freed, so it takes nothing the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from work import head_dim

# leaf ids inside one layer's key
WQ, WK, WV, WO, BQ, BK, BV, ROUTER = 0, 1, 2, 3, 4, 5, 6, 7
DENSE = (8, 9, 10)
SHARED = (11, 12, 13)
EXPERTS = 20
BIAS_SCALE = 0.1  # biases are non-zero so the bias path is compared too


def root_key(seed: int):
    """A key from any non-negative seed, including seeds wider than 32
    bits: the high word is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def layer_key(root, li):
    return jax.random.fold_in(root, 100 + li)


def attn_leaves(root, li, cfg: dict, dtype) -> dict:
    """GQA projections of layer `li` in the program's naming and layout:
    wq [D,H,hd], wk/wv [D,Kv,hd], wo [H,hd,D], biases [H|Kv, hd]."""
    k = layer_key(root, li)
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], head_dim(cfg)
    out = {
        "wq": _normal(jax.random.fold_in(k, WQ), (d, h, hd), d ** -0.5, dtype),
        "wk": _normal(jax.random.fold_in(k, WK), (d, kv, hd), d ** -0.5, dtype),
        "wv": _normal(jax.random.fold_in(k, WV), (d, kv, hd), d ** -0.5, dtype),
        "wo": _normal(jax.random.fold_in(k, WO), (h, hd, d), h ** -0.5 * hd ** -0.5, dtype),
    }
    if cfg["qkv_bias"]:
        out["bq"] = _normal(jax.random.fold_in(k, BQ), (h, hd), BIAS_SCALE, dtype)
        out["bk"] = _normal(jax.random.fold_in(k, BK), (kv, hd), BIAS_SCALE, dtype)
        out["bv"] = _normal(jax.random.fold_in(k, BV), (kv, hd), BIAS_SCALE, dtype)
    return out


def dense_leaves(root, li, cfg: dict, dtype) -> dict:
    k = layer_key(root, li)
    d, f = cfg["d_model"], cfg["d_ff"]
    return {
        "w_gate": _normal(jax.random.fold_in(k, DENSE[0]), (d, f), d ** -0.5, dtype),
        "w_up": _normal(jax.random.fold_in(k, DENSE[1]), (d, f), d ** -0.5, dtype),
        "w_down": _normal(jax.random.fold_in(k, DENSE[2]), (f, d), f ** -0.5, dtype),
    }


def router_leaf(root, li, cfg: dict):
    k = layer_key(root, li)
    d, e = cfg["d_model"], cfg["moe"]["n_experts"]
    return _normal(jax.random.fold_in(k, ROUTER), (d, e), d ** -0.5, jnp.float32)


def shared_leaves(root, li, cfg: dict, dtype) -> dict:
    k = layer_key(root, li)
    d, f, n = cfg["d_model"], cfg["moe"]["d_expert"], cfg["moe"]["n_shared"]
    return {
        "w_gate": _normal(jax.random.fold_in(k, SHARED[0]), (n, d, f), d ** -0.5, dtype),
        "w_up": _normal(jax.random.fold_in(k, SHARED[1]), (n, d, f), d ** -0.5, dtype),
        "w_down": _normal(jax.random.fold_in(k, SHARED[2]), (n, f, d), f ** -0.5, dtype),
    }


def expert_weights(root, li, e, cfg: dict, dtype):
    """Routed expert `e` of layer `li` as [3, D, F]: gate, up, and the
    down projection stored transposed ([D, F]), the tier-buffer layout."""
    d, f = cfg["d_model"], cfg["moe"]["d_expert"]
    key = jax.random.fold_in(jax.random.fold_in(layer_key(root, li), EXPERTS), e)
    scale = jnp.asarray([d ** -0.5, d ** -0.5, f ** -0.5], jnp.float32)
    w = jax.random.normal(key, (3, d, f), jnp.float32) * scale[:, None, None]
    return w.astype(dtype)


def embed_leaf(root, cfg: dict, dtype):
    return _normal(jax.random.fold_in(root, 1), (cfg["vocab_size"], cfg["d_model"]), 1.0, dtype)


def head_leaf(root, cfg: dict, dtype):
    d = cfg["d_model"]
    return _normal(jax.random.fold_in(root, 2), (d, cfg["vocab_size"]), d ** -0.5, dtype)


# ------------------------------------------------------ program layout
def tier_layout(sizes, pad_cold_to: int = 16):
    """Initial tier placement the program's own init uses: experts
    [0, n_hot) hot, then warm, then cold, each at slot = rank in tier;
    the cold buffer is padded to a multiple of `pad_cold_to` slots."""
    n_hot, n_warm, n_cold = (int(x) for x in sizes)
    tier = np.concatenate([np.full(n_hot, 0), np.full(n_warm, 1), np.full(n_cold, 2)])
    slot = np.concatenate([np.arange(n_hot), np.arange(n_warm), np.arange(n_cold)])
    n_cold_slots = -(-n_cold // pad_cold_to) * pad_cold_to
    return tier.astype(np.int32), slot.astype(np.int32), n_cold_slots


def _tier_state(root, li, cfg, sizes, dtype):
    n_hot, n_warm, n_cold = (int(x) for x in sizes)
    tier, slot, n_cold_slots = tier_layout(sizes)

    def buf(first, n, n_slots):
        ids = jnp.arange(first, first + n, dtype=jnp.int32)
        w = jax.lax.map(lambda e: expert_weights(root, li, e, cfg, dtype), ids)
        if n_slots > n:
            w = jnp.concatenate([w, jnp.zeros((n_slots - n, *w.shape[1:]), dtype)])
        return w

    return {
        "hot": buf(0, n_hot, n_hot),
        "warm": buf(n_hot, n_warm, n_warm),
        "cold": buf(n_hot + n_warm, n_cold, n_cold_slots),
        "expert_tier": jnp.asarray(tier),
        "expert_slot": jnp.asarray(slot),
    }


def _program_layer(root, li, cfg, sig, sizes, dtype):
    _, ffn = sig
    d = cfg["d_model"]
    p = {
        "norm1": {"scale": jnp.ones((d,), dtype)},
        "mixer": attn_leaves(root, li, cfg, dtype),
        "norm2": {"scale": jnp.ones((d,), dtype)},
    }
    tiers = None
    if ffn == "moe":
        p["ffn"] = {"router": router_leaf(root, li, cfg)}
        if cfg["moe"]["n_shared"]:
            p["ffn"]["shared"] = shared_leaves(root, li, cfg, dtype)
        tiers = _tier_state(root, li, cfg, sizes, dtype)
    else:
        p["ffn"] = dense_leaves(root, li, cfg, dtype)
    return p, tiers


def program_weights(seed: int, cfg: dict, plan, sigs, sizes):
    """(params, tiered) in the layout `ServingLoop(cfg, params, tiered)`
    takes, made on the default device in one jitted call."""
    return jax.jit(program_weights_fn(cfg, plan, sigs, sizes))(root_key(seed))


def program_weights_fn(cfg: dict, plan, sigs, sizes):
    """The function of a root key that `program_weights` jits.

    `plan` is the program's (unrolled layer indices, n_scan_groups,
    period signatures) for this configuration, `sigs` every layer's
    (mixer, ffn) signature, `sizes` the tier split.
    Routed experts live only in the tier buffers (the params carry the
    router and shared experts), as in a served model."""
    unrolled, n_groups, period = plan
    dtype = jnp.dtype(cfg["param_dtype"])
    offset = len(unrolled)

    def build(root):
        params, tiered = {}, {}
        params["embed"] = {"table": embed_leaf(root, cfg, dtype)}
        for li in unrolled:
            p, t = _program_layer(root, li, cfg, sigs[li], sizes, dtype)
            params[f"layer{li}"] = p
            if t is not None:
                tiered[f"layer{li}"] = t

        def group(g):
            ps, ts = {}, {}
            for j, sig in enumerate(period):
                li = offset + g * len(period) + j
                p, t = _program_layer(root, li, cfg, sig, sizes, dtype)
                ps[f"slot{j}"] = p
                if t is not None:
                    ts[f"slot{j}"] = t
            return ps, ts

        params["stack"], tiered["stack"] = jax.lax.map(
            group, jnp.arange(n_groups, dtype=jnp.int32)
        )
        if not tiered["stack"]:
            del tiered["stack"]
        params["final_norm"] = {"scale": jnp.ones((cfg["d_model"],), dtype)}
        params["head"] = {"w": head_leaf(root, cfg, dtype)}
        return params, tiered

    return build

