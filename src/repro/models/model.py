"""Model assembly: maps a ModelConfig to init/train/prefill/decode fns.

Layers are grouped into periodic stacks (uniform dense stack; deepseek's
dense-first-layer + 59 MoE layers; jamba's 8-layer Mamba/attention blocks;
xlstm's (m,s) pairs) and executed with ``lax.scan`` over stacked params,
so the lowered HLO contains one period body regardless of depth — this is
what makes 32 (arch x shape) x 2 mesh dry-run compiles tractable.

All functions are pure; params/caches are nested dicts. ``init_params``
can be run under ``jax.eval_shape`` for allocation-free dry-runs.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models import moe as moe_lib
from repro.models import xlstm as xl
from repro.models.layers import (
    Params,
    embed,
    init_embedding,
    init_lm_head,
    init_mlp,
    init_rmsnorm,
    lm_head,
    mlp,
    rmsnorm,
    unembed,
)

Sig = Tuple[str, str]  # (mixer, ffn)


# ----------------------------------------------------------- layer plans
def layer_signature(cfg: ModelConfig, i: int) -> Sig:
    if cfg.xlstm is not None:
        kind = cfg.xlstm.pattern[i % len(cfg.xlstm.pattern)]
        return ("mlstm" if kind == "m" else "slstm", "none")
    if cfg.uses_attention_layer(i):
        mixer = "mla" if cfg.mla is not None else "attn"
    else:
        mixer = "mamba"
    if cfg.uses_moe_layer(i):
        ffn = "moe"
    elif cfg.d_ff > 0:
        ffn = "dense"
    else:
        ffn = "none"
    return (mixer, ffn)


def stack_plan(cfg: ModelConfig) -> Tuple[List[int], int, List[Sig]]:
    """Return (unrolled_prefix_indices, n_scan_groups, period_sigs)."""
    sigs = [layer_signature(cfg, i) for i in range(cfg.n_layers)]
    for offset in range(0, 3):
        rest = sigs[offset:]
        for period in range(1, 17):
            if len(rest) % period:
                continue
            pat = rest[:period]
            if all(rest[i] == pat[i % period] for i in range(len(rest))):
                return list(range(offset)), len(rest) // period, pat
    raise ValueError(f"no periodic plan for {cfg.name}: {sigs}")


# ------------------------------------------------------------ layer init
def init_mixer(rng, cfg: ModelConfig, kind: str) -> Params:
    if kind == "attn":
        return attn.init_gqa(rng, cfg)
    if kind == "mla":
        return attn.init_mla(rng, cfg)
    if kind == "mamba":
        return mb.init_mamba(rng, cfg)
    if kind == "mlstm":
        return xl.init_mlstm(rng, cfg)
    if kind == "slstm":
        return xl.init_slstm(rng, cfg)
    raise ValueError(kind)


def init_layer(rng, cfg: ModelConfig, sig: Sig, cross: bool = False) -> Params:
    mixer, ffn = sig
    k1, k2, k3 = jax.random.split(rng, 3)
    dt = jnp.dtype(cfg.param_dtype)
    p: Params = {
        "norm1": init_rmsnorm(cfg.d_model, dt),
        "mixer": init_mixer(k1, cfg, mixer),
    }
    if ffn != "none":
        p["norm2"] = init_rmsnorm(cfg.d_model, dt)
        if ffn == "moe":
            p["ffn"] = moe_lib.init_moe(k2, cfg)
        else:
            p["ffn"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dt)
    if cross:
        p["norm_cross"] = init_rmsnorm(cfg.d_model, dt)
        p["cross"] = attn.init_gqa(k3, cfg)
    return p


def init_params(rng, cfg: ModelConfig) -> Params:
    unrolled_idx, n_groups, period = stack_plan(cfg)
    ks = jax.random.split(rng, 6)
    dt = jnp.dtype(cfg.param_dtype)
    params: Params = {"embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model, dt)}

    cross = cfg.encdec is not None and cfg.encdec.cross_attention
    for j, li in enumerate(unrolled_idx):
        params[f"layer{li}"] = init_layer(
            jax.random.fold_in(ks[1], li), cfg, layer_signature(cfg, li), cross
        )

    def one_group(key):
        sk = jax.random.split(key, len(period))
        return {
            f"slot{j}": init_layer(sk[j], cfg, period[j], cross)
            for j in range(len(period))
        }

    params["stack"] = jax.vmap(one_group)(jax.random.split(ks[2], n_groups))
    params["final_norm"] = init_rmsnorm(cfg.d_model, dt)
    if not cfg.tie_embeddings:
        params["head"] = init_lm_head(ks[3], cfg.d_model, cfg.vocab_size, dt)

    if cfg.encdec is not None:
        enc_sig: Sig = ("attn", "dense")

        def one_enc(key):
            return {"slot0": init_layer(key, cfg, enc_sig, cross=False)}

        params["encoder"] = {
            "stack": jax.vmap(one_enc)(
                jax.random.split(ks[4], cfg.encdec.n_encoder_layers)
            ),
            "final_norm": init_rmsnorm(cfg.d_model, dt),
        }
    return params


# ------------------------------------------------------------ cache init
def init_layer_cache(cfg: ModelConfig, sig: Sig, batch: int, seq: int, cross: bool):
    mixer, _ = sig
    dt = jnp.dtype(cfg.param_dtype)
    hd = cfg.resolved_head_dim
    c: Params = {}
    if mixer == "attn":
        c["k"] = jnp.zeros((batch, seq, cfg.n_kv_heads, hd), dt)
        c["v"] = jnp.zeros((batch, seq, cfg.n_kv_heads, hd), dt)
    elif mixer == "mla":
        m = cfg.mla
        c["ckv"] = jnp.zeros((batch, seq, m.kv_lora_rank), dt)
        c["krope"] = jnp.zeros((batch, seq, m.qk_rope_head_dim), dt)
    elif mixer == "mamba":
        c["state"] = mb.mamba_init_state(cfg, batch, dt)
    elif mixer == "mlstm":
        c["state"] = xl.mlstm_init_state(cfg, batch)
    elif mixer == "slstm":
        c["state"] = xl.slstm_init_state(cfg, batch)
    if cross:
        f = cfg.encdec.frontend_frames
        c["ck"] = jnp.zeros((batch, f, cfg.n_kv_heads, hd), dt)
        c["cv"] = jnp.zeros((batch, f, cfg.n_kv_heads, hd), dt)
    return c


def init_cache(cfg: ModelConfig, batch: int, seq: int):
    unrolled_idx, n_groups, period = stack_plan(cfg)
    cross = cfg.encdec is not None and cfg.encdec.cross_attention
    cache: Params = {}
    for li in unrolled_idx:
        cache[f"layer{li}"] = init_layer_cache(
            cfg, layer_signature(cfg, li), batch, seq, cross
        )

    def stacked(leaf_fn):
        one = {
            f"slot{j}": init_layer_cache(cfg, period[j], batch, seq, cross)
            for j in range(len(period))
        }
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups, *x.shape)).copy(), one
        )

    cache["stack"] = stacked(None)
    return cache


# ---------------------------------------------------------- layer apply
def apply_layer(
    cfg: ModelConfig,
    sig: Sig,
    p: Params,
    x: jnp.ndarray,
    positions,
    *,
    mode: str,  # "full" (train / prefill / encoder) | "decode"
    cache: Params | None = None,
    pos=None,
    causal: bool = True,
    tiered_state: Params | None = None,
    cold_capacity_frac: float = 0.25,
    token_mask: jnp.ndarray | None = None,  # [B, S] valid-token mask
    paged_tables: jnp.ndarray | None = None,  # [B, nb] block tables
    paged_past_len: jnp.ndarray | None = None,  # [B] cached prefix lengths
):
    """Returns (x, aux_loss, expert_counts, new_cache).

    When `tiered_state` is given (serving path of MoE archs), the routed
    experts execute through the TriMoE three-tier runtime
    (serving/tiered_moe.py) instead of the flat training MoE. Either
    way the expert FFN obeys `cfg.moe_backend` (kernels/backend.py):
    "pallas" runs decode steps on the batched expert GEMV and
    prefill/full passes on the fused grouped MoE GEMM; "ref" keeps the
    grouped einsums ("auto" = pallas on TPU, ref elsewhere) — the same
    resolution rule `cfg.paged_attn_backend` uses for attention.

    `token_mask` marks real tokens. In decode mode ([B, 1]) it masks
    dead batch slots out of MoE dispatch/counts. In full mode ([B, S],
    bucketed masked prefill with right padding) it additionally masks
    pad KEYS out of attention and makes the recurrent mixers carry
    state through pad steps, so the returned caches match an unpadded
    forward of each row's real prefix.

    Paged KV (serving/paged_kv.py): `paged_tables` switches attention
    to the block-pool cache — `cache` then carries POOL leaves
    ([N+1, bs, ...]) for k/v/ckv/krope and per-row leaves for recurrent
    state. Decode and full mode share ONE block-sparse paged-attention
    path (kernels/paged_attention — decode is the chunk-of-1 case): in
    full mode the chunk's K/V is scattered into the rows' blocks and
    attention walks each row's table with per-query causal masking
    against `paged_past_len` cached prefix tokens; returned seq leaves
    are the UPDATED POOLS.
    """
    mixer, ffn = sig
    e = cfg.moe.router_width if cfg.moe is not None else 1
    aux = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((e,), jnp.int32)
    new_cache: Params = {}

    fmask = token_mask if mode == "full" else None  # [B, S] prefill mask
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if mixer in ("attn", "mla"):
        if mode == "full" and paged_tables is not None:
            # chunked suffix prefill: write the chunk's K/V into the
            # rows' blocks, then block-sparse paged attention (shared
            # with decode = chunk of 1)
            if mixer == "attn":
                y, pk, pv = attn.gqa_prefill_paged(
                    p["mixer"], cfg, h, cache["k"], cache["v"],
                    paged_tables, paged_past_len, positions, fmask,
                )
                new_cache.update(k=pk, v=pv)
            else:
                y, pc, pk = attn.mla_prefill_paged(
                    p["mixer"], cfg, h, cache["ckv"], cache["krope"],
                    paged_tables, paged_past_len, positions, fmask,
                )
                new_cache.update(ckv=pc, krope=pk)
        elif mode == "full":
            if mixer == "attn":
                y, (k, v) = attn.gqa_forward(
                    p["mixer"], cfg, h, positions, causal=causal,
                    token_mask=fmask,
                )
                if cache is not None:
                    new_cache.update(k=k, v=v)
            else:
                y, (ckv, krope) = attn.mla_forward(
                    p["mixer"], cfg, h, positions, token_mask=fmask,
                )
                if cache is not None:
                    new_cache.update(ckv=ckv, krope=krope)
        elif paged_tables is not None:
            if mixer == "attn":
                y, pk, pv = attn.gqa_decode_paged(
                    p["mixer"], cfg, h, cache["k"], cache["v"],
                    paged_tables, pos,
                )
                new_cache.update(k=pk, v=pv)
            else:
                y, pc, pk = attn.mla_decode_paged(
                    p["mixer"], cfg, h, cache["ckv"], cache["krope"],
                    paged_tables, pos,
                )
                new_cache.update(ckv=pc, krope=pk)
        else:
            if mixer == "attn":
                y, ck, cv = attn.gqa_decode(p["mixer"], cfg, h, cache["k"], cache["v"], pos)
                new_cache.update(k=ck, v=cv)
            else:
                y, cc, ck = attn.mla_decode(
                    p["mixer"], cfg, h, cache["ckv"], cache["krope"], pos
                )
                new_cache.update(ckv=cc, krope=ck)
    elif mixer == "mamba":
        if mode == "full":
            if cache is not None:
                y, st = mb.mamba_forward(
                    p["mixer"], cfg, h, return_state=True, token_mask=fmask
                )
                new_cache["state"] = st
            else:
                y = mb.mamba_forward(p["mixer"], cfg, h, token_mask=fmask)
        else:
            y, st = mb.mamba_decode(p["mixer"], cfg, h, cache["state"])
            new_cache["state"] = st
    elif mixer == "mlstm":
        if mode == "full":
            if cache is not None:
                y, st = xl.mlstm_forward(
                    p["mixer"], cfg, h, return_state=True, token_mask=fmask
                )
                new_cache["state"] = st
            else:
                y = xl.mlstm_forward(p["mixer"], cfg, h, token_mask=fmask)
        else:
            y, st = xl.mlstm_decode(p["mixer"], cfg, h, cache["state"])
            new_cache["state"] = st
    elif mixer == "slstm":
        if mode == "full":
            if cache is not None:
                y, st = xl.slstm_forward(
                    p["mixer"], cfg, h, return_state=True, token_mask=fmask
                )
                new_cache["state"] = st
            else:
                y = xl.slstm_forward(p["mixer"], cfg, h, token_mask=fmask)
        else:
            y, st = xl.slstm_decode(p["mixer"], cfg, h, cache["state"])
            new_cache["state"] = st
    else:
        raise ValueError(mixer)
    x = x + y

    if "cross" in p and cache is not None:
        hc = rmsnorm(p["norm_cross"], x, cfg.norm_eps)
        yc, _ = attn.gqa_forward(
            p["cross"], cfg, hc, positions,
            kv_override=(cache["ck"], cache["cv"]), causal=False,
        )
        x = x + yc
        new_cache.update(ck=cache["ck"], cv=cache["cv"])

    if ffn != "none":
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if ffn == "moe":
            if tiered_state is not None:
                from repro.serving.tiered_moe import tiered_moe_forward

                y_moe, counts = tiered_moe_forward(
                    p["ffn"], tiered_state, cfg, h2,
                    cold_capacity_frac=cold_capacity_frac,
                    token_mask=token_mask,
                )
                x = x + y_moe
            else:
                # masked prefill runs dropless: capacity depends on the
                # PADDED token count, so capacity-bounded dropping would
                # make padded and unpadded prefill diverge
                out = moe_lib.moe_forward(
                    p["ffn"], cfg, h2,
                    full_capacity=(mode == "decode" or token_mask is not None),
                    token_mask=token_mask,
                )
                x = x + out.y
                aux = out.aux_loss
                counts = out.expert_counts
        else:
            x = x + mlp(p["ffn"], h2)
    return x, aux, counts, new_cache


# ------------------------------------------------------------- forwards
def _run_encoder(params: Params, cfg: ModelConfig, frames: jnp.ndarray):
    positions = jnp.arange(frames.shape[1], dtype=jnp.int32)[None, :]
    sig: Sig = ("attn", "dense")

    def body(x, p):
        x, _, _, _ = apply_layer(
            cfg, sig, p["slot0"], x, positions, mode="full", causal=False
        )
        return x, None

    x, _ = jax.lax.scan(body, frames, params["encoder"]["stack"])
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)


def _cross_kv(cfg: ModelConfig, layer_p: Params, enc_out: jnp.ndarray):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, layer_p["cross"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, layer_p["cross"]["wv"])
    return k, v


def _logits(params: Params, cfg: ModelConfig, x: jnp.ndarray):
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    return lm_head(params["head"], x)


def forward_train(
    params: Params, cfg: ModelConfig, batch: Dict[str, Any], remat: bool = True
):
    """batch: {"tokens": [B,S] int32, optional "frames": [B,F,D]}.

    Returns (logits [B,S,V], aux_loss, expert_counts [n_layers_or_groups, E]).
    With `remat`, the layer-scan body is activation-checkpointed (matmul
    outputs without batch dims are saved; everything else recomputes).
    """
    tokens = batch["tokens"]
    unrolled_idx, n_groups, period = stack_plan(cfg)
    x = embed(params["embed"], tokens)
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]

    enc_out = None
    if cfg.encdec is not None:
        enc_out = _run_encoder(params, cfg, batch["frames"])

    aux_total = jnp.zeros((), jnp.float32)
    for li in unrolled_idx:
        p = params[f"layer{li}"]
        cache = None
        if enc_out is not None:
            ck, cv = _cross_kv(cfg, p, enc_out)
            cache = {"ck": ck, "cv": cv}
        x, aux, _, _ = apply_layer(
            cfg, layer_signature(cfg, li), p, x, positions, mode="full", cache=cache
        )
        aux_total = aux_total + aux

    def body(carry, p):
        x, aux_sum = carry
        cnts = []
        for j, sig in enumerate(period):
            lp = p[f"slot{j}"]
            cache = None
            if enc_out is not None:
                ck, cv = _cross_kv(cfg, lp, enc_out)
                cache = {"ck": ck, "cv": cv}
            x, aux, counts, _ = apply_layer(
                cfg, sig, lp, x, positions, mode="full", cache=cache
            )
            aux_sum = aux_sum + aux
            cnts.append(counts)
        return (x, aux_sum), jnp.stack(cnts)

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    (x, aux_total), counts = jax.lax.scan(body, (x, aux_total), params["stack"])
    logits = _logits(params, cfg, x)
    return logits, aux_total, counts.reshape(-1, counts.shape[-1])


def prefill(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, Any],
    cache_len: int | None = None,
    tiered: Params | None = None,
    cold_capacity_frac: float = 0.25,
    token_mask: jnp.ndarray | None = None,
):
    """Full-sequence prefill building the decode cache.

    Returns (last_token_logits [B,V], cache). Attention layers cache
    K/V (plus cross K/V for enc-dec); recurrent mixers (mamba/xlstm)
    cache their final sequence state, so decode continues exactly where
    the parallel form left off (validated in tests/test_models.py).

    `tiered` optionally carries TriMoE tier states (same pytree as
    decode_step's): serving engines hold stripped params (expert weights
    live only in tier buffers), so their prefill must route MoE layers
    through the tiered runtime too.

    `token_mask` [B, S] bool enables bucketed masked prefill: rows are
    RIGHT-padded to a shared bucket width, pad keys are masked out of
    attention, recurrent mixers carry state through pad steps, pad K/V
    cache entries are zeroed, and the returned logits are each row's
    LAST REAL token's (an all-pad row yields row 0's position — callers
    discard those rows). The result is identical to per-row unpadded
    prefill (tests/test_masked_prefill.py, test_bucketed_properties.py).
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    cache_len = cache_len or s
    unrolled_idx, n_groups, period = stack_plan(cfg)
    cross = cfg.encdec is not None and cfg.encdec.cross_attention
    x = embed(params["embed"], tokens)
    positions = jnp.arange(s, dtype=jnp.int32)[None, :]

    enc_out = None
    if cfg.encdec is not None:
        enc_out = _run_encoder(params, cfg, batch["frames"])

    def merge(c: Params, nc: Params) -> Params:
        """Place fresh seq-indexed entries at the head of the ring buffer,
        zeroing pad positions so the cache rows equal unpadded prefill's."""
        out = dict(c)
        for key, val in nc.items():
            if key in ("k", "v", "ckv", "krope"):
                if token_mask is not None and val.shape[1] == s:
                    m = token_mask.reshape(b, s, *([1] * (val.ndim - 2)))
                    val = val * m.astype(val.dtype)
                if val.shape[1] != c[key].shape[1]:
                    val = jax.lax.dynamic_update_slice_in_dim(
                        c[key], val, 0, axis=1
                    )
            out[key] = val
        return out

    cache_out: Params = {}
    for li in unrolled_idx:
        sig = layer_signature(cfg, li)
        p = params[f"layer{li}"]
        c = init_layer_cache(cfg, sig, b, cache_len, cross)
        if enc_out is not None:
            c["ck"], c["cv"] = _cross_kv(cfg, p, enc_out)
        ts = tiered.get(f"layer{li}") if tiered else None
        x, _, _, nc = apply_layer(
            cfg, sig, p, x, positions, mode="full", cache=c,
            tiered_state=ts, cold_capacity_frac=cold_capacity_frac,
            token_mask=token_mask,
        )
        cache_out[f"layer{li}"] = merge(c, nc)

    tiered_stack = tiered.get("stack") if tiered else None

    def body(x, inp):
        p, ts_stack = inp
        new_caches = {}
        for j, sig in enumerate(period):
            lp = p[f"slot{j}"]
            c = init_layer_cache(cfg, sig, b, cache_len, cross)
            if enc_out is not None:
                c["ck"], c["cv"] = _cross_kv(cfg, lp, enc_out)
            ts = ts_stack.get(f"slot{j}") if ts_stack else None
            x, _, _, nc = apply_layer(
                cfg, sig, lp, x, positions, mode="full", cache=c,
                tiered_state=ts, cold_capacity_frac=cold_capacity_frac,
                token_mask=token_mask,
            )
            new_caches[f"slot{j}"] = merge(c, nc)
        return x, new_caches

    x, stack_cache = jax.lax.scan(body, x, (params["stack"], tiered_stack or {}))
    cache_out["stack"] = stack_cache
    if token_mask is None:
        x_last = x[:, -1:, :]
    else:
        # per-row gather of the last REAL token's hidden state
        last = jnp.maximum(token_mask.sum(-1).astype(jnp.int32) - 1, 0)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = _logits(params, cfg, x_last)[:, 0]
    return logits, cache_out


def decode_step(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    cache: Params,
    pos,
    tiered: Params | None = None,
    cold_capacity_frac: float = 0.25,
    token_mask: jnp.ndarray | None = None,
):
    """One decode step. tokens: [B,1] int32; pos: int32 absolute position
    — scalar (all rows aligned) or [B] per-row (continuous batching with
    staggered prompt lengths); the cache is a full ring buffer of the
    shape-spec seq_len. `tiered` optionally carries per-layer TriMoE tier
    states (stacked the same way as params["stack"], keyed by MoE slots
    only). `token_mask` [B] marks live rows: dead (padded) slots are
    excluded from MoE dispatch and expert counts.
    Returns (logits [B,V], new_cache, expert_counts)."""
    unrolled_idx, n_groups, period = stack_plan(cfg)
    x = embed(params["embed"], tokens)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (tokens.shape[0],))
    positions = pos[:, None]
    tmask = None if token_mask is None else token_mask.reshape(-1, 1)

    counts_all = []
    for li in unrolled_idx:
        sig = layer_signature(cfg, li)
        ts = tiered.get(f"layer{li}") if tiered else None
        x, _, counts, nc = apply_layer(
            cfg, sig, params[f"layer{li}"], x, positions,
            mode="decode", cache=cache[f"layer{li}"], pos=pos, tiered_state=ts,
            cold_capacity_frac=cold_capacity_frac, token_mask=tmask,
        )
        cache = {**cache, f"layer{li}": {**cache[f"layer{li}"], **nc}}
        counts_all.append(counts)

    tiered_stack = tiered.get("stack") if tiered else None

    def body(carry, inp):
        x = carry
        p, c, ts_stack = inp
        new_c = {}
        cnts = []
        for j, sig in enumerate(period):
            ts = ts_stack.get(f"slot{j}") if ts_stack else None
            x, _, counts, nc = apply_layer(
                cfg, sig, p[f"slot{j}"], x, positions,
                mode="decode", cache=c[f"slot{j}"], pos=pos, tiered_state=ts,
                cold_capacity_frac=cold_capacity_frac, token_mask=tmask,
            )
            merged = dict(c[f"slot{j}"])
            merged.update(nc)
            new_c[f"slot{j}"] = merged
            cnts.append(counts)
        return x, (new_c, jnp.stack(cnts))

    x, (stack_cache, counts) = jax.lax.scan(
        body, x, (params["stack"], cache["stack"], tiered_stack or {})
    )
    cache = {**cache, "stack": stack_cache}
    logits = _logits(params, cfg, x)[:, 0]
    e = cfg.moe.router_width if cfg.moe is not None else 1
    counts = counts.reshape(-1, e)
    if counts_all:
        counts = jnp.concatenate([jnp.stack(counts_all), counts], axis=0)
    return logits, cache, counts


# ----------------------------------------------------- paged KV variants
# Cache leaves with a sequence dimension — these live in block POOLS
# under the paged layout; everything else (recurrent state, cross K/V)
# stays per-slot (serving/paged_kv.py).
SEQ_CACHE_KEYS = frozenset({"k", "v", "ckv", "krope"})


def _scan_pool_groups(body, x, pools, xs, n_groups: int):
    """`lax.scan` over the stacked scan groups with the stacked KV pools
    in the carry. `body(x, group_pools, group_xs)` returns (x, the
    group's new pools, outputs). Group g's pools are read by index and
    written back in place; as a scan output instead, the new pools would
    be a fresh buffer, which costs a whole-pool copy when the caller
    donates the pools."""

    def step(carry, inp):
        x, pools = carry
        g, group_xs = inp
        mine = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, g, keepdims=False), pools
        )
        x, new, ys = body(x, mine, group_xs)
        pools = jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, g, 0),
            pools, new,
        )
        return (x, pools), ys

    (x, pools), ys = jax.lax.scan(
        step, (x, pools), (jnp.arange(n_groups, dtype=jnp.int32), xs)
    )
    return x, pools, ys


def decode_step_paged(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    pools: Params,
    states: Params,
    tables: jnp.ndarray,
    pos,
    tiered: Params | None = None,
    cold_capacity_frac: float = 0.25,
    token_mask: jnp.ndarray | None = None,
):
    """One decode step against the paged KV cache.

    tokens [B,1]; `pools` holds the shared block pools (seq leaves,
    [N+1, bs, ...]; stack leaves carry the scan-group dim first);
    `states` the active rows' non-seq leaves ([B, ...]); tables [B, nb]
    per-row block tables; pos [B] absolute positions. Returns
    (logits, new_pools, new_states, expert_counts) — mirror of
    `decode_step` with attention layers reading/writing pools by block
    table (attn.gqa_decode_paged / attn.mla_decode_paged)."""
    unrolled_idx, n_groups, period = stack_plan(cfg)
    x = embed(params["embed"], tokens)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (tokens.shape[0],))
    positions = pos[:, None]
    tables = jnp.asarray(tables, jnp.int32)
    tmask = None if token_mask is None else token_mask.reshape(-1, 1)

    new_pools: Params = {}
    new_states: Params = {}
    counts_all = []
    for li in unrolled_idx:
        sig = layer_signature(cfg, li)
        ts = tiered.get(f"layer{li}") if tiered else None
        cache_l = {**pools[f"layer{li}"], **states[f"layer{li}"]}
        x, _, counts, nc = apply_layer(
            cfg, sig, params[f"layer{li}"], x, positions,
            mode="decode", cache=cache_l, pos=pos, tiered_state=ts,
            cold_capacity_frac=cold_capacity_frac, token_mask=tmask,
            paged_tables=tables,
        )
        new_pools[f"layer{li}"] = {
            k: v for k, v in nc.items() if k in SEQ_CACHE_KEYS
        }
        new_states[f"layer{li}"] = {
            **states[f"layer{li}"],
            **{k: v for k, v in nc.items() if k not in SEQ_CACHE_KEYS},
        }
        counts_all.append(counts)

    tiered_stack = tiered.get("stack") if tiered else None

    def body(x, pool_c, inp):
        p, state_c, ts_stack = inp
        np_, ns_ = {}, {}
        cnts = []
        for j, sig in enumerate(period):
            ts = ts_stack.get(f"slot{j}") if ts_stack else None
            cache_l = {**pool_c[f"slot{j}"], **state_c[f"slot{j}"]}
            x, _, counts, nc = apply_layer(
                cfg, sig, p[f"slot{j}"], x, positions,
                mode="decode", cache=cache_l, pos=pos, tiered_state=ts,
                cold_capacity_frac=cold_capacity_frac, token_mask=tmask,
                paged_tables=tables,
            )
            np_[f"slot{j}"] = {
                k: v for k, v in nc.items() if k in SEQ_CACHE_KEYS
            }
            ns_[f"slot{j}"] = {
                **state_c[f"slot{j}"],
                **{k: v for k, v in nc.items() if k not in SEQ_CACHE_KEYS},
            }
            cnts.append(counts)
        return x, np_, (ns_, jnp.stack(cnts))

    x, stack_pools, (stack_states, counts) = _scan_pool_groups(
        body, x, pools["stack"],
        (params["stack"], states["stack"], tiered_stack or {}), n_groups,
    )
    new_pools["stack"] = stack_pools
    new_states["stack"] = stack_states
    logits = _logits(params, cfg, x)[:, 0]
    e = cfg.moe.router_width if cfg.moe is not None else 1
    counts = counts.reshape(-1, e)
    if counts_all:
        counts = jnp.concatenate([jnp.stack(counts_all), counts], axis=0)
    return logits, new_pools, new_states, counts


def prefill_paged(
    params: Params,
    cfg: ModelConfig,
    batch: Dict[str, Any],
    pools: Params,
    tables: jnp.ndarray,
    past_len: jnp.ndarray,
    token_mask: jnp.ndarray,
    tiered: Params | None = None,
    cold_capacity_frac: float = 0.25,
):
    """Suffix-only masked prefill against the paged cache — one chunk
    of the CHUNKED paged-attention path (decode is the chunk-of-1 case
    of the same kernels).

    batch["tokens"] [W, S] carries each row's UNCACHED suffix chunk,
    right-padded to a bucket width and masked by `token_mask`;
    `past_len` [W] is the token count already present in the cache
    before this chunk (0 for cold admissions; a prefix-cache hit or the
    previous piggyback chunk otherwise); tables [W, nbw] are the rows'
    block tables, SLICED by the caller to the pow2 active width
    covering prefix + suffix (engine.prefill_slots_paged) — one compile
    per (suffix bucket, table-width bucket). Attention layers scatter
    the chunk's K/V into its blocks and walk the tables block-sparsely
    with per-query causal masking (attn.gqa/mla_prefill_paged) — the
    cached prefix is never dense-gathered at full table width. Rows
    with past_len > 0 require an attention-only arch (recurrent state
    cannot be reconstructed from a token-keyed prefix —
    serving/paged_kv.py and the loop's chunked_prefill gate this);
    recurrent layers run the ordinary masked forward and return per-row
    state.

    Returns (last_real_token_logits [W, V], new_pools, new_states).
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    unrolled_idx, n_groups, period = stack_plan(cfg)
    assert cfg.encdec is None, "paged prefill does not support enc-dec"
    x = embed(params["embed"], tokens)
    past_len = jnp.asarray(past_len, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    positions = past_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

    def run_layer(p, sig, x, cache_pools, ts):
        mixer, _ = sig
        is_attn = mixer in ("attn", "mla")
        x, _, _, nc = apply_layer(
            cfg, sig, p, x, positions, mode="full",
            cache=cache_pools if is_attn else {},
            tiered_state=ts, cold_capacity_frac=cold_capacity_frac,
            token_mask=token_mask,
            paged_tables=tables if is_attn else None,
            paged_past_len=past_len if is_attn else None,
        )
        new_pool = {k: v for k, v in nc.items() if k in SEQ_CACHE_KEYS}
        new_state = {k: v for k, v in nc.items() if k not in SEQ_CACHE_KEYS}
        return x, new_pool, new_state

    new_pools: Params = {}
    new_states: Params = {}
    for li in unrolled_idx:
        sig = layer_signature(cfg, li)
        ts = tiered.get(f"layer{li}") if tiered else None
        x, npool, nstate = run_layer(
            params[f"layer{li}"], sig, x, pools[f"layer{li}"], ts
        )
        new_pools[f"layer{li}"] = npool
        new_states[f"layer{li}"] = nstate

    tiered_stack = tiered.get("stack") if tiered else None

    def body(x, pool_c, inp):
        p, ts_stack = inp
        np_, ns_ = {}, {}
        for j, sig in enumerate(period):
            ts = ts_stack.get(f"slot{j}") if ts_stack else None
            x, npool, nstate = run_layer(
                p[f"slot{j}"], sig, x, pool_c[f"slot{j}"], ts
            )
            np_[f"slot{j}"] = npool
            ns_[f"slot{j}"] = nstate
        return x, np_, ns_

    x, stack_pools, stack_states = _scan_pool_groups(
        body, x, pools["stack"], (params["stack"], tiered_stack or {}),
        n_groups,
    )
    new_pools["stack"] = stack_pools
    new_states["stack"] = stack_states
    last = jnp.maximum(token_mask.sum(-1).astype(jnp.int32) - 1, 0)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = _logits(params, cfg, x_last)[:, 0]
    return logits, new_pools, new_states


def decode_verify(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,
    pools: Params,
    tables: jnp.ndarray,
    past_len: jnp.ndarray,
    token_mask: jnp.ndarray,
    tiered: Params | None = None,
    cold_capacity_frac: float = 0.25,
):
    """Speculative chunk-of-k verification against the paged cache.

    Identical forward to `prefill_paged` — tokens [W, K] carry each
    row's [sampled token, draft_1..draft_{k-1}] chunk at vector
    positions past_len + [0..K), right-padded and masked per row by
    `token_mask` (per-row draft counts differ) — but it keeps what
    prefill throws away: the logits at EVERY chunk position (the accept
    rule compares draft i against argmax of position i-1's logits) and
    the per-layer expert counts (a verify step feeds the tier scheduler
    exactly like the decode step it replaces). In fp32 the chunk-of-k
    logits are bit-exact vs k sequential decode steps: decode is the
    chunk-of-1 case of the same kernel family.

    Returns (logits [W, K, V], new_pools, new_states, expert_counts).
    """
    b, s = tokens.shape
    unrolled_idx, n_groups, period = stack_plan(cfg)
    assert cfg.encdec is None, "paged verify does not support enc-dec"
    x = embed(params["embed"], tokens)
    past_len = jnp.asarray(past_len, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    positions = past_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]

    def run_layer(p, sig, x, cache_pools, ts):
        mixer, _ = sig
        is_attn = mixer in ("attn", "mla")
        x, _, counts, nc = apply_layer(
            cfg, sig, p, x, positions, mode="full",
            cache=cache_pools if is_attn else {},
            tiered_state=ts, cold_capacity_frac=cold_capacity_frac,
            token_mask=token_mask,
            paged_tables=tables if is_attn else None,
            paged_past_len=past_len if is_attn else None,
        )
        new_pool = {k: v for k, v in nc.items() if k in SEQ_CACHE_KEYS}
        new_state = {k: v for k, v in nc.items() if k not in SEQ_CACHE_KEYS}
        return x, new_pool, new_state, counts

    new_pools: Params = {}
    new_states: Params = {}
    counts_all = []
    for li in unrolled_idx:
        sig = layer_signature(cfg, li)
        ts = tiered.get(f"layer{li}") if tiered else None
        x, npool, nstate, counts = run_layer(
            params[f"layer{li}"], sig, x, pools[f"layer{li}"], ts
        )
        new_pools[f"layer{li}"] = npool
        new_states[f"layer{li}"] = nstate
        counts_all.append(counts)

    tiered_stack = tiered.get("stack") if tiered else None

    def body(x, pool_c, inp):
        p, ts_stack = inp
        np_, ns_ = {}, {}
        cnts = []
        for j, sig in enumerate(period):
            ts = ts_stack.get(f"slot{j}") if ts_stack else None
            x, npool, nstate, counts = run_layer(
                p[f"slot{j}"], sig, x, pool_c[f"slot{j}"], ts
            )
            np_[f"slot{j}"] = npool
            ns_[f"slot{j}"] = nstate
            cnts.append(counts)
        return x, np_, (ns_, jnp.stack(cnts))

    x, stack_pools, (stack_states, counts) = _scan_pool_groups(
        body, x, pools["stack"], (params["stack"], tiered_stack or {}),
        n_groups,
    )
    new_pools["stack"] = stack_pools
    new_states["stack"] = stack_states
    logits = _logits(params, cfg, x)
    e = cfg.moe.router_width if cfg.moe is not None else 1
    counts = counts.reshape(-1, e)
    if counts_all:
        counts = jnp.concatenate([jnp.stack(counts_all), counts], axis=0)
    return logits, new_pools, new_states, counts
