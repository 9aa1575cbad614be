"""Architecture `gqa_moe`: pre-norm GQA attention with rotary
embeddings, a SwiGLU FFN that is dense or a softmax-top-k routed mixture
with optional shared experts, a final norm and an output head.

A configuration file names its architecture under `reference`, and the
harness loads bench/references/<reference>.py by that name. The module
gives three things:

- `model_config(cfg)`: the program's ModelConfig for the file;
- `program_weights(seed, cfg, mcfg, sizes)`: the seeded weights in the
  layout the program's ServingLoop takes (bench/weights.py);
- `Reference` and `served_gaps`: the plain reference and the check.

The reference is a straightforward float32 forward, written in jax.numpy
with HIGHEST matmul precision. It imports nothing of the program: the
weights are drawn again from the seed by bench/weights.py, layer by
layer, after the program's state is freed, and the routed experts are
computed densely (every expert on every token, weighted by the router's
renormalised top-k gates), in blocks of experts so that any width fits.

The control (`quant=True`) is the same forward one precision below what
the configuration states: every bfloat16 operand of a matmul (weights,
activations, queries, keys and values) is rounded to float8 e4m3 with a
per-row scale along the contraction axis, and the float32 router's
input is rounded to bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
import work

HI = jax.lax.Precision.HIGHEST
EXPERT_BLOCK = 8  # routed experts drawn and applied per scan step
E4M3_MAX = 448.0
NEG = -1e30


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, quant, a_axis, b_axis):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant:
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HI)


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    """Rotate-half rotary embedding over the whole head. x [B,S,H,hd]."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[None, :, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def model_config(cfg: dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig, MoEConfig

    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["norm_eps"],
        qkv_bias=cfg["qkv_bias"], tie_embeddings=cfg["tie_embeddings"],
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["param_dtype"],
        moe=MoEConfig(**cfg["moe"]),
    )


def program_weights(seed: int, cfg: dict, mcfg, sizes):
    """(params, tiered) for `ServingLoop(mcfg, params, tiered)`, made on
    the device in one jitted call from the seed."""
    from repro.models.model import layer_signature, stack_plan

    sigs = [layer_signature(mcfg, i) for i in range(mcfg.n_layers)]
    return W.program_weights(seed, cfg, stack_plan(mcfg), sigs, sizes)


class Reference:
    """Float32 forward of one configuration, layer by layer."""

    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg["param_dtype"])
        e = cfg["moe"]["n_experts"]
        self.expert_block = max(b for b in range(1, min(EXPERT_BLOCK, e) + 1) if e % b == 0)
        self._layer = jax.jit(self._layer_impl, static_argnames=("moe", "quant"))
        self._embed = jax.jit(self._embed_impl)
        self._head = jax.jit(self._head_impl, static_argnames=("quant",))

    # ------------------------------------------------------------ parts
    def _embed_impl(self, root, tokens):
        return W.embed_leaf(root, self.cfg, self.dtype).astype(jnp.float32)[tokens]

    def _attention(self, root, li, h, quant):
        cfg = self.cfg
        p = W.attn_leaves(root, li, cfg, self.dtype)
        b, s, _ = h.shape
        q = _mm("bsd,dhk->bshk", h, p["wq"], quant, -1, 0)
        k = _mm("bsd,dhk->bshk", h, p["wk"], quant, -1, 0)
        v = _mm("bsd,dhk->bshk", h, p["wv"], quant, -1, 0)
        if cfg["qkv_bias"]:
            q = q + p["bq"].astype(jnp.float32)
            k = k + p["bk"].astype(jnp.float32)
            v = v + p["bv"].astype(jnp.float32)
        pos = jnp.arange(s, dtype=jnp.int32)
        q = _rope(q, pos, cfg["rope_theta"])
        k = _rope(k, pos, cfg["rope_theta"])
        group = cfg["n_heads"] // cfg["n_kv_heads"]
        k = jnp.repeat(k, group, axis=2)  # head h reads kv head h // group
        v = jnp.repeat(v, group, axis=2)
        scores = _mm("bshk,bthk->bhst", q, k, quant, -1, -1) * q.shape[-1] ** -0.5
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(causal[None, None], scores, NEG)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _mm("bhst,bthk->bshk", probs, v, quant, -1, 1)
        return _mm("bshk,hkd->bsd", out, p["wo"], quant, (-2, -1), (0, 1))

    def _swiglu(self, h, wg, wu, wd_t, quant):
        """h [T,D]; wg/wu [D,F]; wd_t [D,F] (down projection transposed)."""
        g = _mm("td,df->tf", h, wg, quant, -1, 0)
        u = _mm("td,df->tf", h, wu, quant, -1, 0)
        a = jax.nn.silu(g) * u
        return _mm("tf,df->td", a, wd_t, quant, -1, 1)

    def _moe(self, root, li, h, quant):
        cfg, mo = self.cfg, self.cfg["moe"]
        t = h.shape[0]
        router = W.router_leaf(root, li, cfg)
        hr = h.astype(jnp.bfloat16).astype(jnp.float32) if quant else h
        logits = jnp.einsum("td,de->te", hr, router, precision=HI)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, mo["top_k"])
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        gates = jnp.zeros((t, mo["n_experts"]), jnp.float32)
        gates = gates.at[jnp.arange(t)[:, None], idx].add(w)
        nb = self.expert_block

        def block(y, first):
            for j in range(nb):
                e = first + j
                ew = W.expert_weights(root, li, e, cfg, self.dtype)
                out = self._swiglu(h, ew[0], ew[1], ew[2], quant)
                y = y + gates[:, e][:, None] * out
            return y, None

        y, _ = jax.lax.scan(block, jnp.zeros_like(h),
                            jnp.arange(0, mo["n_experts"], nb, dtype=jnp.int32))
        if mo["n_shared"]:
            sp = W.shared_leaves(root, li, cfg, self.dtype)
            for j in range(mo["n_shared"]):
                y = y + self._swiglu(h, sp["w_gate"][j], sp["w_up"][j],
                                     sp["w_down"][j].T, quant)
        return y

    def _layer_impl(self, x, root, li, *, moe, quant):
        cfg = self.cfg
        b, s, d = x.shape
        x = x + self._attention(root, li, _rmsnorm(x, cfg["norm_eps"]), quant)
        h = _rmsnorm(x, cfg["norm_eps"]).reshape(b * s, d)
        if moe:
            y = self._moe(root, li, h, quant)
        else:
            p = W.dense_leaves(root, li, cfg, self.dtype)
            y = self._swiglu(h, p["w_gate"], p["w_up"], p["w_down"].T, quant)
        return x + y.reshape(b, s, d)

    def _head_impl(self, x, root, picks, *, quant):
        """Per position: the best logit, the logits of the tokens in
        `picks`, and the argmax. x [B,S,D]; picks [P,B,S]."""
        w = W.head_leaf(root, self.cfg, self.dtype)

        def one(args):
            xr, pr = args  # [S,D], [P,S]
            logits = _mm("sd,dv->sv", _rmsnorm(xr, self.cfg["norm_eps"]), w, quant, -1, 0)
            at = jnp.take_along_axis(logits[None], pr[:, :, None], axis=2)[:, :, 0]
            return jnp.max(logits, axis=-1), at, jnp.argmax(logits, axis=-1).astype(jnp.int32)

        best, at, arg = jax.lax.map(one, (x, jnp.swapaxes(picks, 0, 1)))
        return best, jnp.swapaxes(at, 0, 1), arg

    # ------------------------------------------------------------- api
    def run(self, seed: int, tokens: np.ndarray, picks: np.ndarray, quant: bool = False):
        """tokens [B,S] int32 (each row causal on its own); picks [P,B,S]
        token ids whose logits to read at each position. Returns numpy
        (best logit [B,S], logits of the picks [P,B,S], argmax [B,S])."""
        root = W.root_key(seed)
        x = self._embed(root, jnp.asarray(tokens))
        for li in range(self.cfg["n_layers"]):
            moe = work.is_moe_layer(self.cfg, li)
            x = self._layer(x, root, jnp.int32(li), moe=moe, quant=quant)
        best, at, arg = self._head(x, root, jnp.asarray(picks), quant=quant)
        return np.asarray(best), np.asarray(at), np.asarray(arg)


def served_gaps(ref: Reference, seed: int, seqs, length: int):
    """Gaps below the reference's best logit of the served tokens and of
    the tokens the control (`quant=True`) puts first, at the same
    positions.

    `seqs` is a list of (prompt, served) int arrays. Each row is the
    prompt followed by the served tokens but the last, padded to
    `length`; position p of a row predicts row[p + 1]. Returns numpy
    arrays over every served token: `served` and `lower`."""
    b = len(seqs)
    tokens = np.zeros((b, length), np.int32)
    target = np.zeros((b, length), np.int32)
    mask = np.zeros((b, length), bool)
    for i, (prompt, served) in enumerate(seqs):
        full = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served, np.int32)])
        n = len(full) - 1
        if n > length:
            raise ValueError(f"sequence of {n} positions exceeds {length}")
        tokens[i, :n] = full[:-1]
        target[i, :n] = full[1:]
        mask[i, len(prompt) - 1:n] = True
    _, _, lower_tok = ref.run(seed, tokens, target[None], quant=True)
    best, at, _ = ref.run(seed, tokens, np.stack([target, lower_tok]))
    return {"served": (best - at[0])[mask], "lower": (best - at[1])[mask]}
