"""Shared layers: norms, rotary embeddings, SwiGLU, embeddings.

Everything is functional: params are nested dicts of jnp arrays; each
layer is ``init_*(rng, cfg) -> params`` + ``apply(params, x, ...) -> y``.
Models stack per-layer params along a leading axis and ``lax.scan`` over
them, which keeps the HLO one-layer-sized (critical for the 512-device
dry-run compiles).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


def dense_init(rng, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) > 1 else 1
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ----------------------------------------------------------------- RMSNorm
def init_rmsnorm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# ------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               frac: float = 1.0) -> jnp.ndarray:
    """x: [..., seq, n_heads, head_dim]; positions: [..., seq].

    Rotate-half over the first `frac * head_dim` dims of each head, with
    frequencies over those dims; the rest pass through unrotated
    (partial rotary, GLM-4.5's `partial_rotary_factor`)."""
    rot = int(round(x.shape[-1] * frac))
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], positions, theta), x[..., rot:]], axis=-1)
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- SwiGLU
def init_mlp(rng, d_model: int, d_ff: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), dtype),
        "w_up": dense_init(k2, (d_model, d_ff), dtype),
        "w_down": dense_init(k3, (d_ff, d_model), dtype),
    }


def mlp(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# ------------------------------------------------------------- Embeddings
def init_embedding(rng, vocab: int, d_model: int, dtype) -> Params:
    return {"table": dense_init(rng, (vocab, d_model), dtype, scale=1.0)}


def embed(params: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...d,vd->...v", x, params["table"])


def init_lm_head(rng, d_model: int, vocab: int, dtype) -> Params:
    return {"w": dense_init(rng, (d_model, vocab), dtype)}


def lm_head(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("...d,dv->...v", x, params["w"])
