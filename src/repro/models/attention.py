"""Attention: GQA/MQA/MHA and DeepSeek-style MLA, for train/prefill/decode.

Decode uses a ring-buffer KV cache of static length S (the shape spec's
``seq_len``): steady-state decoding of one new token against a full
context window, which is exactly what the ``decode_*`` cells lower.

MLA decode uses the *absorbed* formulation (scores and values computed
directly against the compressed latent cache) so the per-token cache is
kv_lora_rank + rope_dim = 576 values — the property the paper's KV-offload
story relies on.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.backend import KernelBackend, kernel_span
from repro.models.layers import Params, apply_rope, dense_init

NEG_INF = -1e30

# --- sequence-parallel attention (§Perf) -----------------------------
# When set (launch/dryrun.py --seq-parallel, or engines on real meshes),
# full-sequence causal self-attention runs under shard_map with query
# rows sharded over `axis`: chips whose head count does not divide the
# model axis stop replicating the O(S^2) score computation and instead
# each compute their S/m query slice against gathered K/V.
_SEQ_PARALLEL = None  # (mesh, axis_name, dp_axes) | None


def set_sequence_parallel(mesh, axis: str = "model", dp=("data",)):
    global _SEQ_PARALLEL
    _SEQ_PARALLEL = (mesh, axis, dp) if mesh is not None else None


# ------------------------------------------------------------------ init
def init_gqa(rng, cfg) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.n_heads, cfg.n_kv_heads
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d, h, hd), dt),
        "wk": dense_init(ks[1], (d, kv, hd), dt),
        "wv": dense_init(ks[2], (d, kv, hd), dt),
        "wo": dense_init(ks[3], (h, hd, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dt)
        p["bk"] = jnp.zeros((kv, hd), dt)
        p["bv"] = jnp.zeros((kv, hd), dt)
    return p


def init_mla(rng, cfg) -> Params:
    m, d = cfg.mla, cfg.d_model
    dt = jnp.dtype(cfg.param_dtype)
    h = cfg.n_heads
    ks = jax.random.split(rng, 4)
    return {
        # q: direct projection to nope+rope dims per head
        "wq": dense_init(ks[0], (d, h, m.qk_nope_head_dim + m.qk_rope_head_dim), dt),
        # kv_a: down-projection to latent + shared rope key
        "wkv_a": dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), dt),
        # kv_b: latent -> per-head (k_nope, v)
        "wkv_b": dense_init(
            ks[2], (m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim), dt
        ),
        "wo": dense_init(ks[3], (h, m.v_head_dim, d), dt),
    }


# ------------------------------------------------- grouped core attention
def _grouped_attention(
    q, k, v, *, causal: bool = False, valid=None, q_chunk: int = 1024,
    q_offset=None,
):
    """q:[B,Sq,H,hd] k/v:[B,Sk,Kv,hd_{k,v}].

    Scans over query chunks so the [*, Sq, Sk] score tensor never
    materializes beyond one chunk (flash-style, exact row softmax); the
    causal mask is built per-chunk from iota — never a [Sq, Sk] tensor
    (at 32k that would be a replicated 1 GB constant).

    `valid`: optional [Sk] bool of usable key slots (decode ring buffer).
    Causal convention: query i sits at absolute position i + (Sk - Sq),
    or q_offset + i when `q_offset` is given (sequence-parallel shards).
    """
    if (
        _SEQ_PARALLEL is not None
        and causal
        and q_offset is None
        and valid is None
        and q.shape[1] == k.shape[1]
    ):
        sp = _seq_parallel_attention(q, k, v, q_chunk=q_chunk)
        if sp is not None:
            return sp
    b, sq, h, hdk = q.shape
    sk = k.shape[1]
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, kvh, g, hdk)
    scale = hdk ** -0.5
    kpos = jnp.arange(sk)

    def attend(qc, start):
        # qc: [B, C, Kv, G, hd]; start: scalar chunk offset into Sq
        s = jnp.einsum("bckgd,bskd->bckgs", qc, k).astype(jnp.float32) * scale
        mask = None
        if causal:
            base = q_offset if q_offset is not None else (sk - sq)
            qpos = start + jnp.arange(qc.shape[1]) + base
            mask = kpos[None, :] <= qpos[:, None]  # [C, Sk]
        if mask is not None:
            mask = mask[None]  # [1, C, Sk]
        if valid is not None:
            # valid: [Sk] shared, or [B, Sk] per-row (continuous batching:
            # slots in one decode group sit at different absolute positions)
            vmask = valid[None, None, :] if valid.ndim == 1 else valid[:, None, :]
            mask = vmask if mask is None else (mask & vmask)
        if mask is not None:
            s = jnp.where(mask[:, :, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bckgs,bskd->bckgd", p.astype(v.dtype), v)

    if sq <= q_chunk:
        out = attend(qg, 0)
    else:
        n = sq // q_chunk
        assert sq % q_chunk == 0, (sq, q_chunk)
        qs = qg.reshape(b, n, q_chunk, kvh, g, hdk).transpose(1, 0, 2, 3, 4, 5)
        starts = jnp.arange(n) * q_chunk

        def body(_, inp):
            qc, start = inp
            return None, attend(qc, start)

        _, out = jax.lax.scan(body, None, (qs, starts))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, kvh, g, -1)
    return out.reshape(b, sq, h, -1)


def _seq_parallel_attention(q, k, v, *, q_chunk: int):
    """shard_map causal self-attention: query rows sharded over the model
    axis, K/V gathered once per layer. Returns None when shapes don't
    divide (caller falls back to the replicated path)."""
    from jax.sharding import PartitionSpec as P

    mesh, axis, dp = _SEQ_PARALLEL
    m = mesh.shape[axis]
    b, sq, h, hd = q.shape
    if sq % m or sq // m < 1:
        return None
    dpa = dp if len(dp) > 1 else dp[0]
    bspec = dpa if b % max(
        1, int(np.prod([mesh.shape[a] for a in (dp if isinstance(dp, tuple) else (dp,))]))
    ) == 0 else None

    def local(qs, kf, vf):
        idx = jax.lax.axis_index(axis)
        offset = idx * qs.shape[1]
        return _grouped_attention(
            qs, kf, vf, causal=True, q_chunk=min(q_chunk, qs.shape[1]),
            q_offset=offset,
        )

    spec_q = P(bspec, axis, None, None)
    spec_kv = P(bspec, None, None, None)
    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=spec_q, check_vma=False,
    )
    return fn(q, k, v)


# ------------------------------------------------------------------- GQA
def gqa_forward(p: Params, cfg, x, positions, *, kv_override=None, causal=True,
                token_mask=None):
    """Full-sequence attention (train / prefill / encoder / cross).

    `token_mask` [B, S] bool marks real tokens (bucketed masked prefill):
    pad positions are excluded as KEYS, so real queries never attend to
    padding; outputs at pad query positions are unspecified.

    Suffix-only prefill against a cached paged context goes through
    `gqa_prefill_paged` (the chunked block-sparse path — decode shares
    the same kernel at chunk 1), not this function.

    Returns (out, (k, v)) — the tokens' k/v in [B, S, Kv, hd] layout
    for caching.
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
        if "bq" in p:
            q = q + p["bq"]
    out = _grouped_attention(q, k, v, causal=causal, valid=token_mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k, v)


def gqa_decode(p: Params, cfg, x, cache_k, cache_v, pos):
    """One-token decode against a ring-buffer cache.

    x: [B, 1, D]; cache_k/v: [B, S, Kv, hd]; pos: int32 scalar or [B] —
    the absolute position of each row's new token (per-row positions are
    the continuous-batching case: slots hold requests with staggered
    prompt lengths). The oldest entry (slot pos % S) is overwritten
    first, then attention runs over the full window.
    """
    b = x.shape[0]
    s_max = cache_k.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posv = pos[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    slot = jnp.mod(pos, s_max)
    rows = jnp.arange(b)
    cache_k = cache_k.at[rows, slot].set(k[:, 0])
    cache_v = cache_v.at[rows, slot].set(v[:, 0])
    # slot-validity mask: before the ring wraps, tail slots are empty
    valid = jnp.arange(s_max)[None, :] <= posv
    out = _grouped_attention(q, cache_k, cache_v, valid=valid)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


# ------------------------------------------------------------------- MLA
def mla_forward(p: Params, cfg, x, positions, *, token_mask=None):
    """Full-sequence MLA (train / prefill). `token_mask` as in
    gqa_forward: pad keys masked for bucketed masked prefill.

    Standard path expands the latent to per-head K/V. Under sequence
    parallelism the ABSORBED formulation runs instead (§Perf): scores and
    values are computed directly against the 576-wide latent, so the
    shard_map KV gather moves ckv/krope (~150 MB/layer) instead of the
    expanded per-head K/V (~4.3 GB/layer).

    Suffix-only prefill against cached paged latents goes through
    `mla_prefill_paged` (the absorbed chunked path — decode shares the
    same kernel at chunk 1), not this function.

    Returns (out, (ckv, krope)) — the tokens' compressed cache entries.
    """
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv, krope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    krope = apply_rope(krope[:, :, None, :], positions, cfg.rope_theta)  # [B,S,1,rd]

    if _SEQ_PARALLEL is not None:
        wk_b, wv_b = jnp.split(p["wkv_b"], [m.qk_nope_head_dim], axis=-1)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk_b)  # absorb W_k^nope
        q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B,S,H,r+rd]
        k_eff = jnp.concatenate([ckv[:, :, None, :], krope], axis=-1)
        # _grouped_attention scales by (r+rd)^-0.5; correct to d_qk^-0.5
        d_qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        q_eff = q_eff * ((m.kv_lora_rank + m.qk_rope_head_dim) / d_qk) ** 0.5
        o_lat = _grouped_attention(
            q_eff, k_eff, ckv[:, :, None, :], causal=True, valid=token_mask
        )  # [B,S,H,r]
        out = jnp.einsum("bshr,rhk->bshk", o_lat, wv_b)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (ckv, krope[:, :, 0, :])

    kvb = jnp.einsum("bsr,rhk->bshk", ckv, p["wkv_b"])
    k_nope, v = jnp.split(kvb, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope, (*k_nope.shape[:3], m.qk_rope_head_dim))],
        axis=-1,
    )
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = _grouped_attention(qf, k, v, causal=True, valid=token_mask)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (ckv, krope[:, :, 0, :])


def mla_decode(p: Params, cfg, x, cache_ckv, cache_krope, pos):
    """Absorbed MLA decode: score/value against the latent cache directly.

    cache_ckv: [B, S, r]; cache_krope: [B, S, rope_dim]; pos: int32
    scalar or [B] per-row absolute positions (continuous batching).

    The absorbed matmuls accumulate in fp32: folding W_k^nope into q
    makes every score a ~kv_lora_rank-wide latent contraction, and a
    bf16 accumulation there drifts decode measurably away from the
    expanded prefill/train path (the deepseek seed failure in
    tests/test_models.py).
    """
    m = cfg.mla
    s_max = cache_ckv.shape[1]
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posv = pos[:, None]

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])  # [B,1,H,nope+rope]
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, posv, cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv_new, krope_new = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    krope_new = apply_rope(krope_new[:, :, None, :], posv, cfg.rope_theta)[:, :, 0, :]
    slot = jnp.mod(pos, s_max)
    rows = jnp.arange(b)
    cache_ckv = cache_ckv.at[rows, slot].set(ckv_new[:, 0])
    cache_krope = cache_krope.at[rows, slot].set(krope_new[:, 0])

    wk_b, wv_b = jnp.split(p["wkv_b"], [m.qk_nope_head_dim], axis=-1)
    # absorb W_k^nope into q: [B,1,H,r]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk_b,
                       preferred_element_type=jnp.float32)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    s = (
        jnp.einsum("bshr,btr->bhst", q_lat, cache_ckv,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bshk,btk->bhst", q_rope, cache_krope,
                     preferred_element_type=jnp.float32)
    ) * scale
    valid = jnp.arange(s_max)[None, :] <= posv  # [B, S]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", pattn, cache_ckv,
                       preferred_element_type=jnp.float32)  # [B,1,H,r]
    o = jnp.einsum("bshr,rhk->bshk", o_lat, wv_b,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), cache_ckv, cache_krope


# ----------------------------------------- paged (block-table) attention
def _paged_backend(cfg, backend):
    """Resolve the paged decode-attention backend: an explicit `backend`
    overrides `cfg.paged_attn_backend` ("auto" = Pallas kernel on TPU,
    dense-gather ref elsewhere; "pallas" forces the kernel, interpret
    mode off-TPU, so CPU CI exercises the kernel path)."""
    from repro.kernels.paged_attention import resolve_backend

    return resolve_backend(backend or getattr(cfg, "paged_attn_backend", "auto"))


def paged_gather(pool: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """Linearize each row's blocks: pool [N(+1), bs, ...] gathered by
    tables [B, nb] -> [B, nb*bs, ...]. Invalid table entries point at
    the trash block and are excluded by the caller's position mask.
    Delegates to the kernel package's single linearization contract."""
    from repro.kernels.paged_attention.ref import linearize_blocks

    return linearize_blocks(pool, tables)


def _paged_write(pool, tables, pos, val):
    """Scatter one new token per row into its block: val [B, ...] lands
    at pool[tables[b, pos[b] // bs], pos[b] % bs]. Dead rows carry
    all-trash tables, so their writes fall into the sentinel block."""
    bs = pool.shape[1]
    rows = jnp.arange(tables.shape[0])
    bid = tables[rows, pos // bs]
    return pool.at[bid, pos % bs].set(val)


def paged_scatter(pool, tables, gpos, mask, val):
    """Scatter a CHUNK of new-token seq entries into block pools.

    pool [N+1, bs, ...]; tables [W, nb]; gpos [W, C] global positions
    (past_len + i); mask [W, C] real tokens; val [W, C, ...]. Masked
    (pad) positions write to the trash block (last pool row), so a
    right-padded chunk never pollutes a live block — the chunk-width
    generalization of `_paged_write`'s dead-row contract."""
    bs = pool.shape[1]
    trash = pool.shape[0] - 1
    lb = jnp.minimum(gpos // bs, tables.shape[1] - 1)
    bid = jnp.take_along_axis(tables, lb, axis=1)  # [W, C]
    bid = jnp.where(mask, bid, trash)
    return pool.at[bid, gpos % bs].set(val)


def gqa_decode_paged(p: Params, cfg, x, pool_k, pool_v, tables, pos,
                     backend=None):
    """One-token GQA decode against a paged (block-pool) cache.

    x: [B, 1, D]; pool_k/pool_v: [N+1, bs, Kv, hd] shared block pools
    (last block is the write trash for dead rows); tables: [B, nb]
    int32 per-row block tables; pos: int32 [B] absolute positions.

    The new token's K/V is written to its row's tail block, then
    attention runs over the row's blocks with the same per-row position
    mask as the contiguous path — same numerics as `gqa_decode` for any
    block layout (tests/test_paged_kv.py). `backend` (default
    `cfg.paged_attn_backend`) picks the block-sparse Pallas kernel
    (kernels/paged_attention — walks only each row's blocks, online
    softmax) or the dense-gather reference, which linearizes the full
    table width. Shared (prefix-cache) blocks are full and immutable,
    so the post-write read can never see another row's in-flight token.
    """
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posv = pos[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = apply_rope(q, posv, cfg.rope_theta)
    k = apply_rope(k, posv, cfg.rope_theta)
    pool_k = _paged_write(pool_k, tables, pos, k[:, 0])
    pool_v = _paged_write(pool_v, tables, pos, v[:, 0])
    kind, interpret = _paged_backend(cfg, backend)
    with kernel_span("paged_decode_gqa", KernelBackend(kind, interpret)):
        if kind == "pallas":
            from repro.kernels.paged_attention import paged_decode_gqa

            kvh = pool_k.shape[2]
            qk = q[:, 0].reshape(b, kvh, q.shape[2] // kvh, q.shape[3])
            out = paged_decode_gqa(
                qk, pool_k, pool_v, tables, pos, interpret=interpret
            ).reshape(b, 1, q.shape[2], q.shape[3])
        else:
            keys = paged_gather(pool_k, tables)  # [B, nb*bs, Kv, hd]
            vals = paged_gather(pool_v, tables)
            valid = jnp.arange(keys.shape[1])[None, :] <= posv
            out = _grouped_attention(q, keys, vals, valid=valid)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), pool_k, pool_v


def mla_decode_paged(p: Params, cfg, x, pool_ckv, pool_krope, tables, pos,
                     backend=None):
    """Absorbed MLA decode against paged latent pools.

    pool_ckv: [N+1, bs, r]; pool_krope: [N+1, bs, rope_dim]; tables:
    [B, nb]; pos: [B]. Same math (and fp32 accumulation) as
    `mla_decode` over the row's blocks; `backend` as in
    `gqa_decode_paged` — the Pallas kernel attends in latent space and
    the wv_b expansion stays out here."""
    m = cfg.mla
    b = x.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    posv = pos[:, None]

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, posv, cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv_new, krope_new = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    krope_new = apply_rope(krope_new[:, :, None, :], posv, cfg.rope_theta)[:, :, 0, :]
    pool_ckv = _paged_write(pool_ckv, tables, pos, ckv_new[:, 0])
    pool_krope = _paged_write(pool_krope, tables, pos, krope_new[:, 0])

    wk_b, wv_b = jnp.split(p["wkv_b"], [m.qk_nope_head_dim], axis=-1)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk_b,
                       preferred_element_type=jnp.float32)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    kind, interpret = _paged_backend(cfg, backend)
    with kernel_span("paged_decode_mla", KernelBackend(kind, interpret)):
        if kind == "pallas":
            from repro.kernels.paged_attention import paged_decode_mla

            o_lat = paged_decode_mla(
                q_lat[:, 0], q_rope[:, 0].astype(jnp.float32), pool_ckv,
                pool_krope, tables, pos, scale=scale, interpret=interpret,
            )[:, None]  # [B,1,H,r] fp32
        else:
            cache_ckv = paged_gather(pool_ckv, tables)  # [B, nb*bs, r]
            cache_krope = paged_gather(pool_krope, tables)
            s = (
                jnp.einsum("bshr,btr->bhst", q_lat, cache_ckv,
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bshk,btk->bhst", q_rope, cache_krope,
                             preferred_element_type=jnp.float32)
            ) * scale
            valid = jnp.arange(cache_ckv.shape[1])[None, :] <= posv
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
            pattn = jax.nn.softmax(s, axis=-1)
            o_lat = jnp.einsum("bhst,btr->bshr", pattn, cache_ckv,
                               preferred_element_type=jnp.float32)
    o = jnp.einsum("bshr,rhk->bshk", o_lat, wv_b,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), pool_ckv, pool_krope


# -------------------------------------------- paged chunked suffix prefill
def gqa_prefill_paged(p: Params, cfg, x, pool_k, pool_v, tables, past_len,
                      positions, token_mask, backend=None):
    """Chunked suffix prefill against the paged cache — the same
    write-then-attend contract as `gqa_decode_paged`, widened to a
    `[rows, chunk]` query tile (decode is this path at chunk 1).

    x: [W, C, D] — each row's uncached-suffix chunk, right-padded;
    pool_k/pool_v: [N+1, bs, Kv, hd]; tables: [W, nb] block tables
    SLICED by the caller to the pow2 active width covering every row's
    prefix + suffix end; past_len: [W] tokens already cached before the
    chunk; positions: [W, C] absolute positions (past_len + arange);
    token_mask: [W, C] real tokens (None = all real).

    The chunk's K/V is scattered into its rows' blocks first (pads to
    the trash block), then attention walks each row's blocks with
    per-query causal masking — the cached prefix AND the chunk's own
    earlier tokens are both just pool reads, which is what makes the
    path identical for cold admission, prefix-hit suffixes, and
    mid-prompt piggyback chunks. `backend` as in `gqa_decode_paged`.

    Returns (out [W, C, D], pool_k, pool_v).
    """
    b, c, _ = x.shape
    past_len = jnp.asarray(past_len, jnp.int32)
    mask = (
        jnp.ones((b, c), bool) if token_mask is None
        else jnp.asarray(token_mask, bool)
    )
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    pool_k = paged_scatter(pool_k, tables, positions, mask, k)
    pool_v = paged_scatter(pool_v, tables, positions, mask, v)
    lengths = mask.sum(-1).astype(jnp.int32)
    kvh = pool_k.shape[2]
    qk = q.reshape(b, c, kvh, q.shape[2] // kvh, q.shape[3])
    kind, interpret = _paged_backend(cfg, backend)
    with kernel_span("paged_prefill_gqa", KernelBackend(kind, interpret)):
        if kind == "pallas":
            from repro.kernels.paged_attention import paged_prefill_gqa

            out = paged_prefill_gqa(
                qk, pool_k, pool_v, tables, past_len, lengths,
                interpret=interpret,
            )
        else:
            from repro.kernels.paged_attention import paged_prefill_gqa_ref

            out = paged_prefill_gqa_ref(qk, pool_k, pool_v, tables, past_len)
    out = out.reshape(b, c, q.shape[2], q.shape[3]).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), pool_k, pool_v


def mla_prefill_paged(p: Params, cfg, x, pool_ckv, pool_krope, tables,
                      past_len, positions, token_mask, backend=None):
    """Absorbed chunked MLA suffix prefill against paged latent pools —
    `mla_decode_paged` widened to a `[rows, chunk]` query tile, same
    fp32 accumulation and latent-space value read (wv_b expansion out
    here). Arguments as in `gqa_prefill_paged` with pools
    [N+1, bs, r | rope_dim]. Returns (out [W, C, D], pools)."""
    m = cfg.mla
    b, c, _ = x.shape
    past_len = jnp.asarray(past_len, jnp.int32)
    mask = (
        jnp.ones((b, c), bool) if token_mask is None
        else jnp.asarray(token_mask, bool)
    )
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv_new, krope_new = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    krope_new = apply_rope(
        krope_new[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]
    pool_ckv = paged_scatter(pool_ckv, tables, positions, mask, ckv_new)
    pool_krope = paged_scatter(pool_krope, tables, positions, mask, krope_new)
    lengths = mask.sum(-1).astype(jnp.int32)

    wk_b, wv_b = jnp.split(p["wkv_b"], [m.qk_nope_head_dim], axis=-1)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, wk_b,
                       preferred_element_type=jnp.float32)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    kind, interpret = _paged_backend(cfg, backend)
    with kernel_span("paged_prefill_mla", KernelBackend(kind, interpret)):
        if kind == "pallas":
            from repro.kernels.paged_attention import paged_prefill_mla

            o_lat = paged_prefill_mla(
                q_lat, q_rope.astype(jnp.float32), pool_ckv, pool_krope,
                tables, past_len, lengths, scale=scale, interpret=interpret,
            )
        else:
            from repro.kernels.paged_attention import paged_prefill_mla_ref

            o_lat = paged_prefill_mla_ref(
                q_lat, q_rope.astype(jnp.float32), pool_ckv, pool_krope,
                tables, past_len, scale=scale,
            )
    o = jnp.einsum("bshr,rhk->bshk", o_lat, wv_b,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"]), pool_ckv, pool_krope
