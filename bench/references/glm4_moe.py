"""Architecture `glm4_moe`: GLM-4.5's block (transformers'
`modeling_glm4_moe.py`), as one expert-parallel chip's share of it.

- Attention: pre-norm GQA with q/k/v biases and no o bias; rotate-half
  rotary embeddings on the first `rotary_frac` of each head's dims, with
  frequencies over those dims; the rest of the head passes unrotated.
- FFN: a dense SwiGLU in the leading layers (`layer_pattern`
  "all_but_first"), then a routed mixture plus shared experts. The router
  gives float32 logits over all `n_routed` experts and scores them with a
  sigmoid; the top-k of the scores plus a learned selection bias are
  chosen, and their gates are the unbiased scores over their sum (+1e-20),
  as `norm_topk_prob` true and `routed_scaling_factor` 1.0 make them.
  `n_group` = `topk_group` = 1, so no group-limited routing.
- The share: this chip holds experts [expert_offset, expert_offset +
  n_experts) of each layer. The routed part of the output is that of the
  held experts; what experts held on other chips would add is left out,
  as the program leaves it out, and the shared experts run on every
  token.

The module gives what bench/references/gqa_moe.py gives (`model_config`,
`program_weights`, `Reference` and `served_gaps`), and reuses that
module's float32 forward for the embedding, the dense layers, the layer
order, the head and the check. Weights come from bench/weights.py's
leaf functions: the router at its published width (a configuration dict
whose `n_experts` is `n_routed`), the held experts by global id. The
selection bias is drawn here, from its own leaf id of each layer's key.
Attention is computed one sequence at a time, so that 96 heads over a
1152-token context fit the chip beside the weights.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp

import weights as W

_spec = importlib.util.spec_from_file_location(
    "bench_references_gqa_moe", os.path.join(os.path.dirname(__file__), "gqa_moe.py"))
gqa = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(gqa)

HI = gqa.HI
SELECTION_BIAS = 30  # leaf id inside a layer's key (bench/weights.py uses 0-20)
served_gaps = gqa.served_gaps


def _wide(cfg: dict) -> dict:
    """The configuration with the router's width as its expert count,
    for bench/weights.py's router leaf."""
    return {**cfg, "moe": {**cfg["moe"], "n_experts": cfg["moe"]["n_routed"]}}


def selection_bias_leaf(root, li, cfg: dict):
    key = jax.random.fold_in(W.layer_key(root, li), SELECTION_BIAS)
    n = cfg["moe"]["n_routed"]
    return jax.random.normal(key, (n,), jnp.float32) * W.BIAS_SCALE


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig

    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], rotary_frac=cfg["rotary_frac"],
        norm_eps=cfg["norm_eps"], qkv_bias=cfg["qkv_bias"],
        tie_embeddings=cfg["tie_embeddings"], param_dtype=cfg["param_dtype"],
        compute_dtype=cfg["param_dtype"], moe=MoEConfig(**cfg["moe"]),
    )


def _tier_state(root, li, cfg: dict, sizes, dtype):
    """Tier buffers of the held experts, drawn by global id into their
    initial slots; tables with one entry per router output, experts
    held on other chips in the program's `AWAY_T` tier."""
    from repro.serving.tiered_moe import AWAY_T

    mo = cfg["moe"]
    first = mo["expert_offset"]
    tier, slot, n_cold_slots = W.tier_layout(sizes)
    n_hot, n_warm, n_cold = (int(x) for x in sizes)

    def buf(lo, n, n_slots):
        ids = jnp.arange(first + lo, first + lo + n, dtype=jnp.int32)
        w = jax.lax.map(lambda e: W.expert_weights(root, li, e, cfg, dtype), ids)
        return jnp.concatenate([w, jnp.zeros((n_slots - n, *w.shape[1:]), dtype)])

    away = mo["n_routed"] - mo["n_experts"] - first
    return {
        "hot": buf(0, n_hot, n_hot),
        "warm": buf(n_hot, n_warm, n_warm),
        "cold": buf(n_hot + n_warm, n_cold, n_cold_slots),
        "expert_tier": jnp.pad(jnp.asarray(tier), (first, away), constant_values=AWAY_T),
        "expert_slot": jnp.pad(jnp.asarray(slot), (first, away)),
    }


def program_weights(seed: int, cfg: dict, mcfg, sizes):
    """(params, tiered) for `ServingLoop(mcfg, params, tiered)`, made on
    the device in one jitted call from the seed: bench/weights.py's
    layout, with each MoE layer's router at its published width, its
    selection bias, and this chip's experts."""
    from repro.models.model import layer_signature, stack_plan

    unrolled, n_groups, period = plan = stack_plan(mcfg)
    sigs = [layer_signature(mcfg, i) for i in range(mcfg.n_layers)]
    base = W.program_weights_fn(cfg, plan, sigs, sizes)
    dtype = jnp.dtype(cfg["param_dtype"])

    def moe_parts(root, li):
        router = {"router": W.router_leaf(root, li, _wide(cfg)),
                  "router_bias": selection_bias_leaf(root, li, cfg)}
        return router, _tier_state(root, li, cfg, sizes, dtype)

    def stacked(parts):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *parts)

    def build(root):
        params, _ = base(root)
        tiered = {}
        for li in unrolled:
            if sigs[li][1] == "moe":
                router, tiered[f"layer{li}"] = moe_parts(root, li)
                params[f"layer{li}"]["ffn"].update(router)
        for j, sig in enumerate(period):
            if sig[1] != "moe":
                continue
            parts = [moe_parts(root, len(unrolled) + g * len(period) + j)
                     for g in range(n_groups)]
            params["stack"][f"slot{j}"]["ffn"].update(stacked([r for r, _ in parts]))
            tiered.setdefault("stack", {})[f"slot{j}"] = stacked([t for _, t in parts])
        return params, tiered

    return jax.jit(build)(W.root_key(seed))


def _partial_rope(x, theta, frac):
    """gqa_moe's rotate-half on the first `frac` of each head's dims."""
    rot = int(round(x.shape[-1] * frac))
    pos = jnp.arange(x.shape[1], dtype=jnp.int32)
    return jnp.concatenate([gqa._rope(x[..., :rot], pos, theta), x[..., rot:]], axis=-1)


class Reference(gqa.Reference):
    """Float32 forward of one configuration, layer by layer."""

    def _attention(self, root, li, h, quant):
        cfg = self.cfg
        p = W.attn_leaves(root, li, cfg, self.dtype)
        group = cfg["n_heads"] // cfg["n_kv_heads"]
        mm = gqa._mm

        def one(hr):  # one sequence [S, D], kept as a batch of one
            hr = hr[None]
            q = mm("bsd,dhk->bshk", hr, p["wq"], quant, -1, 0) + p["bq"].astype(jnp.float32)
            k = mm("bsd,dhk->bshk", hr, p["wk"], quant, -1, 0) + p["bk"].astype(jnp.float32)
            v = mm("bsd,dhk->bshk", hr, p["wv"], quant, -1, 0) + p["bv"].astype(jnp.float32)
            q = _partial_rope(q, cfg["rope_theta"], cfg["rotary_frac"])
            k = _partial_rope(k, cfg["rope_theta"], cfg["rotary_frac"])
            k = jnp.repeat(k, group, axis=2)  # head h reads kv head h // group
            v = jnp.repeat(v, group, axis=2)
            s = hr.shape[1]
            scores = mm("bshk,bthk->bhst", q, k, quant, -1, -1) * q.shape[-1] ** -0.5
            causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            scores = jnp.where(causal[None, None], scores, gqa.NEG)
            probs = jax.nn.softmax(scores, axis=-1)
            out = mm("bhst,bthk->bshk", probs, v, quant, -1, 1)
            return mm("bshk,hkd->bsd", out, p["wo"], quant, (-2, -1), (0, 1))[0]

        return jax.lax.map(one, h)

    def _moe(self, root, li, h, quant):
        cfg, mo = self.cfg, self.cfg["moe"]
        t = h.shape[0]
        router = W.router_leaf(root, li, _wide(cfg))
        bias = selection_bias_leaf(root, li, cfg)
        hr = h.astype(jnp.bfloat16).astype(jnp.float32) if quant else h
        scores = jax.nn.sigmoid(jnp.einsum("td,de->te", hr, router, precision=HI))
        _, idx = jax.lax.top_k(scores + bias, mo["top_k"])
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        gates = jnp.zeros((t, mo["n_routed"]), jnp.float32)
        gates = gates.at[jnp.arange(t)[:, None], idx].add(w)
        first, nb = mo["expert_offset"], self.expert_block

        def block(y, lo):  # held experts lo..lo+nb, by global id
            for j in range(nb):
                e = first + lo + j
                ew = W.expert_weights(root, li, e, cfg, self.dtype)
                y = y + gates[:, e][:, None] * self._swiglu(h, ew[0], ew[1], ew[2], quant)
            return y, None

        y, _ = jax.lax.scan(block, jnp.zeros_like(h),
                            jnp.arange(0, mo["n_experts"], nb, dtype=jnp.int32))
        sp = W.shared_leaves(root, li, cfg, self.dtype)
        for j in range(mo["n_shared"]):
            y = y + self._swiglu(h, sp["w_gate"][j], sp["w_up"][j], sp["w_down"][j].T, quant)
        return y
