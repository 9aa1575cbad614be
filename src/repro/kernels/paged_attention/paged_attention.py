"""Block-sparse paged-attention Pallas TPU kernels — one chunked family.

The serving path stores K/V in a shared pool of fixed-size token blocks
addressed through per-slot block tables (serving/paged_kv.py). Dense
reference semantics linearize each row's FULL table
(`blocks_per_slot * block_size` positions) before attending, so every
step pays O(max_ctx) HBM traffic per token regardless of the row's
actual length — exactly the GPU I/O penalty TriMoE's tiering is built
to hide.

These kernels instead WALK the block table: grid dimension `j` iterates
logical blocks, a scalar-prefetch copy of the table steers each step's
pool DMA to the row's physical block, and `pl.when` skips every block
past the row's last needed position, carrying a flash-style online
softmax (running max / denominator / fp32 accumulator) across the
blocks that do run.

ONE kernel per arch family covers both serving phases. The query tile
is `[rows, chunk]`: chunked SUFFIX PREFILL processes a whole chunk of
`C` new tokens per row, with query `i` sitting at absolute position
`past_len[row] + i` and masked causally against every key position
(cached prefix blocks AND the chunk's own tokens, already scattered
into the pool by the caller — write-then-attend, exactly like decode).
DECODE is the chunk-of-1 degenerate case (`past_len = pos`,
`lengths = 1`), exposed through thin wrappers that keep the historical
decode signatures.

Dead rows follow the trash-block contract: their tables point every
logical block at the sentinel trash block, the kernel attends over its
(finite) garbage, and the caller discards the output — no
special-casing, no NaNs (block 0 always runs, and key position 0 is
causally visible to every query, so the denominator never collapses —
this also covers all-pad prefill rows whose `lengths` is 0).

Two variants:
  * GQA — pools [N+1, bs, Kv, hd]; each grid step loads one whole
    block, every kv head included (the TPU needs a block's last two
    dims to be the pool's own), and queries are grouped per kv head so
    the MQA/GQA head-sharing reads each K/V block once;
  * MLA — absorbed attention over the (ckv, krope) latent pool layout;
    scores are q_lat . ckv + q_rope . krope and the output is the
    latent-space attention read (o_lat), with the wv_b expansion left
    to the caller (models/attention.py) exactly as in `mla_decode`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ------------------------------------------------------------------- GQA
def _gqa_kernel(tables_ref, past_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref, *, bs, g):
    del tables_ref  # consumed by the BlockSpec index maps only
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    past = past_ref[b]
    last = past + len_ref[b] - 1  # the row's last real query position

    # block-sparse walk: blocks wholly past the row's last needed
    # position never run; block 0 always runs so all-pad rows (last < 0)
    # still produce a finite (discarded) output
    @pl.when((j == 0) | (j * bs <= last))
    def _block():
        shape = (q_ref.shape[2], bs)  # [C*G, bs] scores per kv head
        # causal masking at per-query absolute positions: query row
        # r covers chunk token r // G sitting at past + r // G
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qpos = past + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // g
        visible = kpos <= qpos
        # the block carries every kv head (the TPU tiling needs the
        # pool's last two dims whole), so heads are a static loop
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h]           # [C*G, hd]
            k = k_ref[0, :, h, :]     # [bs, hd]
            v = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            s *= q.shape[-1] ** -0.5
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_gqa(
    q: jnp.ndarray,        # [B, C, Kv, G, hd] a chunk of query tokens
    pool_k: jnp.ndarray,   # [N+1, bs, Kv, hd] (last block = write trash)
    pool_v: jnp.ndarray,   # [N+1, bs, Kv, hd]
    tables: jnp.ndarray,   # [B, nb] int32 physical block per logical block
    past_len: jnp.ndarray,  # [B] int32 tokens already cached before chunk
    lengths: jnp.ndarray,  # [B] int32 real (non-pad) tokens in the chunk
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    b, c, kv, g, hd = q.shape
    bs = pool_k.shape[1]
    nb = tables.shape[1]
    # head-major query tile [B, Kv, C*G, hd]: one kv head's queries are
    # a contiguous 2-D slab in the kernel (row r = chunk token r // G)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(b, kv, c * g, hd)
    kern = functools.partial(_gqa_kernel, bs=bs, g=g)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec(
                    (1, kv, c * g, hd), lambda bi, j, t, p, n: (bi, 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, bs, kv, hd), lambda bi, j, t, p, n: (t[bi, j], 0, 0, 0)
                ),
                pl.BlockSpec(
                    (1, bs, kv, hd), lambda bi, j, t, p, n: (t[bi, j], 0, 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, kv, c * g, hd), lambda bi, j, t, p, n: (bi, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((kv, c * g, 1), jnp.float32),
                pltpu.VMEM((kv, c * g, 1), jnp.float32),
                pltpu.VMEM((kv, c * g, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kv, c * g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(past_len, jnp.int32),
      jnp.asarray(lengths, jnp.int32), qh, pool_k, pool_v)
    return out.reshape(b, kv, c, g, hd).transpose(0, 2, 1, 3, 4)


def paged_decode_gqa(
    q: jnp.ndarray,        # [B, Kv, G, hd] one query token per row
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    tables: jnp.ndarray,
    pos: jnp.ndarray,      # [B] int32 absolute position of the new token
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode = chunk of 1 through the chunked kernel: the query sits at
    `pos` with everything at kpos <= pos visible, which is exactly
    `past_len = pos, lengths = 1`."""
    pos = jnp.asarray(pos, jnp.int32)
    return paged_prefill_gqa(
        q[:, None], pool_k, pool_v, tables, pos, jnp.ones_like(pos),
        interpret=interpret,
    )[:, 0]


# ------------------------------------------------------------------- MLA
def _mla_kernel(tables_ref, past_ref, len_ref, ql_ref, qr_ref, ckv_ref,
                kr_ref, o_ref, m_ref, l_ref, acc_ref, *, bs, c, h, scale):
    del tables_ref
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    past = past_ref[b]
    last = past + len_ref[b] - 1

    @pl.when((j == 0) | (j * bs <= last))
    def _block():
        ql = ql_ref[0].reshape(c * h, ql_ref.shape[-1])  # [C*H, r]
        qr = qr_ref[0].reshape(c * h, qr_ref.shape[-1])  # [C*H, rd]
        ckv = ckv_ref[0]    # [bs, r]
        kr = kr_ref[0]      # [bs, rd]
        s = (
            jnp.dot(ql, ckv.T, preferred_element_type=jnp.float32)
            + jnp.dot(qr, kr.T, preferred_element_type=jnp.float32)
        ) * scale
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = past + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // h
        s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        # value read stays in latent space (absorbed formulation)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, ckv.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _done():
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).reshape(c, h, o_ref.shape[-1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_mla(
    q_lat: jnp.ndarray,      # [B, C, H, r] absorbed (W_k^nope-folded)
    q_rope: jnp.ndarray,     # [B, C, H, rd]
    pool_ckv: jnp.ndarray,   # [N+1, bs, r]
    pool_krope: jnp.ndarray,  # [N+1, bs, rd]
    tables: jnp.ndarray,     # [B, nb]
    past_len: jnp.ndarray,   # [B]
    lengths: jnp.ndarray,    # [B]
    *,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    b, c, h, r = q_lat.shape
    rd = q_rope.shape[-1]
    bs = pool_ckv.shape[1]
    nb = tables.shape[1]
    kern = functools.partial(_mla_kernel, bs=bs, c=c, h=h, scale=scale)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nb),
            in_specs=[
                pl.BlockSpec((1, c, h, r), lambda bi, j, t, p, n: (bi, 0, 0, 0)),
                pl.BlockSpec((1, c, h, rd), lambda bi, j, t, p, n: (bi, 0, 0, 0)),
                pl.BlockSpec(
                    (1, bs, r), lambda bi, j, t, p, n: (t[bi, j], 0, 0)
                ),
                pl.BlockSpec(
                    (1, bs, rd), lambda bi, j, t, p, n: (t[bi, j], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, c, h, r), lambda bi, j, t, p, n: (bi, 0, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((c * h, 1), jnp.float32),
                pltpu.VMEM((c * h, 1), jnp.float32),
                pltpu.VMEM((c * h, r), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, c, h, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(past_len, jnp.int32),
      jnp.asarray(lengths, jnp.int32), q_lat, q_rope, pool_ckv, pool_krope)


def paged_decode_mla(
    q_lat: jnp.ndarray,      # [B, H, r]
    q_rope: jnp.ndarray,     # [B, H, rd]
    pool_ckv: jnp.ndarray,
    pool_krope: jnp.ndarray,
    tables: jnp.ndarray,
    pos: jnp.ndarray,        # [B]
    *,
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed MLA decode = chunk of 1 through the chunked kernel."""
    pos = jnp.asarray(pos, jnp.int32)
    return paged_prefill_mla(
        q_lat[:, None], q_rope[:, None], pool_ckv, pool_krope, tables,
        pos, jnp.ones_like(pos), scale=scale, interpret=interpret,
    )[:, 0]
