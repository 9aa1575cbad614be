"""Block-sparse paged-attention Pallas TPU kernels — one chunked family.

The serving path stores K/V in a shared pool of fixed-size token blocks
addressed through per-slot block tables (serving/paged_kv.py). Dense
reference semantics linearize each row's FULL table
(`blocks_per_slot * block_size` positions) before attending, so every
step pays O(max_ctx) HBM traffic per token regardless of the row's
actual length — exactly the GPU I/O penalty TriMoE's tiering is built
to hide.

These kernels instead WALK the block table and stop at each row's
last needed position, carrying a flash-style online softmax (running
max / denominator / fp32 accumulator) across what they read:

  * GQA: one grid step per row. The pools stay in HBM; the kernel
    copies the row's blocks itself, P consecutive logical blocks per
    step of an in-kernel loop (P * bs up to GROUP_TOKENS, fewer where
    VMEM runs short: `group_blocks`), one DMA per block steered by the
    scalar-prefetched table, into a [P*bs, ...] VMEM buffer. Two
    buffers alternate: the next group's copies (or the next row's
    first group's) fly while the current group is scored in
    [r*C*G, SCORE_TOKENS] tiles per lane row of r kv heads (below),
    only those that hold needed blocks. A row costs ceil(needed blocks
    / P) steps and nothing past its length.
  * MLA: grid dimension `j` iterates logical blocks, the table steers
    each step's pool DMA, and `pl.when` skips every block past the
    row's last needed position.

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
  * GQA — pools [N+1, bs, Kv, hd], read through `lane_view` as
    [N+1, bs, Kv/r, r*hd]: r heads share a 128-lane row (granite's
    64-wide heads two to a row), because the TPU's DMA cannot slice a
    block whose rows are half empty out of HBM. Each copy carries every
    kv head, and queries are grouped per lane row (block-diagonal over
    its r heads), so the MQA/GQA head sharing reads each K/V block once;
  * MLA — absorbed attention over the (ckv, krope) latent pool layout;
    scores are q_lat . ckv + q_rope . krope and the output is the
    latent-space attention read (o_lat), with the wv_b expansion left
    to the caller (models/attention.py) exactly as in `mla_decode`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ------------------------------------------------------------------- GQA
# Tokens of KV one step of the GQA walk copies, the tokens it scores at
# a time (a step scores only the tiles that hold needed blocks), and the
# VMEM the kernel may plan for: v5e's default scoped VMEM is 16 MiB, and
# the compiler keeps some for its own temporaries.
GROUP_TOKENS = 256
SCORE_TOKENS = 128
VMEM_BUDGET = 12 * 2**20


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a [rows, cols] slab, padded to the TPU's tiling
    (8 sublanes of 32 bits, packed for narrower types; 128 lanes)."""
    sub = 8 * (4 // itemsize)
    return -(-rows // sub) * sub * (-(-cols // 128) * 128) * itemsize


def head_rows(kv: int, hd: int) -> int:
    """Kv heads per lane row of the kernel's view of a pool block: as
    many as fill 128 lanes (granite's 64-wide heads go two to a row,
    128-wide heads one), and a divisor of `kv`."""
    r = max(1, min(kv, 128 // hd))
    while kv % r:
        r -= 1
    return r


def lane_view(x: jnp.ndarray) -> jnp.ndarray:
    """x [..., Kv, hd] as the GQA kernel reads a pool: [..., Kv/r,
    r*hd], r = `head_rows`. A pool block's narrow heads (hd 64) leave
    half of every 128-lane row empty, and the TPU's DMA cannot slice
    such a block out of HBM; two heads to a row it can. Writers of the
    pools write through this view too, so that the pool the kernel
    reads is the one they wrote, with no relayout between them."""
    kv, hd = x.shape[-2:]
    r = head_rows(kv, hd)
    return x.reshape(*x.shape[:-2], kv // r, r * hd)


def group_blocks(bs: int, nb: int, kv: int, qrows: int, hd: int,
                 itemsize: int) -> int:
    """Pool blocks P the GQA kernel copies per step of its walk.

    P * bs aims at GROUP_TOKENS, is never wider than the table (`nb`
    blocks), and halves while the K/V double buffers, the query and
    output tiles (`qrows` = chunk x G rows per kv head), the fp32
    accumulator and one step's score tiles overrun VMEM_BUDGET. The
    engine's `attn_kv_steps` counter uses the same rule."""
    r = head_rows(kv, hd)
    rows, lanes, tile_q = kv // r, r * hd, r * qrows
    p = max(1, min(nb, GROUP_TOKENS // bs))

    def vmem(p):
        span = p * bs
        kvbuf = 2 * 2 * span * _tile_bytes(rows, lanes, itemsize)
        qo = 2 * 2 * rows * _tile_bytes(tile_q, lanes, itemsize)
        acc = rows * (_tile_bytes(tile_q, lanes, 4)
                      + 2 * _tile_bytes(tile_q, 1, 4))
        return kvbuf + qo + acc + 4 * _tile_bytes(tile_q, span, 4)

    while p > 1 and vmem(p) > VMEM_BUDGET:
        p //= 2
    return p


def kv_walk(last, bs: int, nb: int, p: int):
    """(blocks, steps) the GQA kernel walks for rows whose last real
    query sits at `last` (int array; -1 for an all-pad row): a row
    needs the blocks through its last query, block 0 at least, and no
    more than the table holds, in ceil(blocks / P) steps."""
    need = np.clip(np.maximum(np.asarray(last), 0) // bs + 1, 1, nb)
    return int(need.sum()), int((-(-need // p)).sum())


def _gqa_kernel(tables_ref, past_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                k_buf, v_buf, sems, slot_ref, m_ref, l_ref, acc_ref, *,
                bs, cg, g, hd, p, sub):
    b, rows = pl.program_id(0), pl.num_programs(0)
    nb = tables_ref.shape[1]
    span = p * bs

    def need(row):
        """Blocks row `row` reads: through its last real query, at least
        block 0 (so all-pad rows, whose last is -1, still run), at most
        the table's width."""
        last = past_ref[row] + len_ref[row] - 1
        return jnp.minimum(jnp.maximum(last, 0) // bs + 1, nb)

    def copies(row, grp, slot, act):
        """Start (or wait for) the K and V copies of group `grp` of row
        `row` into buffer `slot`: one DMA per needed block, each block
        carrying every kv head, block i of the group landing at tokens
        [i*bs, (i+1)*bs) of the buffer."""
        first = grp * p

        def one(i, carry):
            blk = tables_ref[row, first + i]
            dst = pl.ds(i * bs, bs)
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                act(pltpu.make_async_copy(
                    hbm.at[blk], buf.at[slot, dst], sems.at[slot]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(p, need(row) - first), one, 0)

    start = lambda c: c.start()  # noqa: E731
    wait = lambda c: c.wait()  # noqa: E731

    @pl.when(b == 0)
    def _first_row():
        # buffer tokens past a row's last block are never copied; zeros
        # (and later, earlier rows' blocks) keep them finite, so masked
        # scores times V stay exactly 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0
        copies(0, 0, 0, start)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    n_grp = (need(b) + p - 1) // p
    shape = (q_ref.shape[2], sub)  # [r*C*G, sub] scores per lane row
    # causal masking at per-query absolute positions: tile row i is
    # query (i % CG) of one of the row's heads, chunk token (i % CG) // G
    # sitting at past + that token
    row_i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    qpos = past_ref[b] + (row_i % cg) // g
    kcol = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def group(grp, carry):
        slot = slot_ref[0]

        # double buffering: the next group's copies (this row's, else
        # the next row's first) fly while this group is computed
        @pl.when(grp + 1 < n_grp)
        def _next_group():
            copies(b, grp + 1, 1 - slot, start)

        @pl.when((grp + 1 == n_grp) & (b + 1 < rows))
        def _next_row():
            copies(b + 1, 0, 1 - slot, start)

        copies(b, grp, slot, wait)

        def tile(u, carry):
            visible = grp * span + u * sub + kcol <= qpos
            for j in range(q_ref.shape[1]):
                # lane row j holds r heads side by side; the query tile
                # is block-diagonal over them, so one product scores all r
                q = q_ref[0, j]                                # [r*C*G, r*hd]
                k = k_buf[slot, pl.ds(u * sub, sub), j, :]     # [sub, r*hd]
                v = v_buf[slot, pl.ds(u * sub, sub), j, :]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                s *= hd ** -0.5
                s = jnp.where(visible, s, NEG_INF)
                m_prev = m_ref[j]
                m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
                pr = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[j] = l_ref[j] * alpha + pr.sum(-1, keepdims=True)
                acc_ref[j] = acc_ref[j] * alpha + jnp.dot(
                    pr.astype(v.dtype), v, preferred_element_type=jnp.float32
                )
                m_ref[j] = m_new
            return carry

        # score only the tiles that hold this group's needed blocks
        held = jnp.minimum(p, need(b) - grp * p) * bs
        jax.lax.fori_loop(0, (held + sub - 1) // sub, tile, 0)
        slot_ref[0] = 1 - slot
        return carry

    jax.lax.fori_loop(0, n_grp, group, 0)
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
    cg, r = c * g, head_rows(kv, hd)
    rows = kv // r
    p = group_blocks(bs, nb, kv, cg, hd, pool_k.dtype.itemsize)
    # head-major query tile, block-diagonal over the r heads of a lane
    # row: [B, Kv/r, r*C*G, r*hd], tile row a*CG + i holds head a's query
    # row i (chunk token i // G) in lanes [a*hd, (a+1)*hd)
    qh = q.transpose(0, 2, 1, 3, 4).reshape(b, rows, r, cg, hd)
    eye = jnp.eye(r, dtype=q.dtype)[:, None, :, None]  # [a, 1, e, 1]
    qd = (qh[:, :, :, :, None] * eye).reshape(b, rows, r * cg, r * hd)
    # score tiles of SCORE_TOKENS where they divide the group
    sub = SCORE_TOKENS if (p * bs) % SCORE_TOKENS == 0 else p * bs
    kern = functools.partial(_gqa_kernel, bs=bs, cg=cg, g=g, hd=hd, p=p,
                             sub=sub)
    tile = pl.BlockSpec((1, rows, r * cg, r * hd),
                        lambda bi, t, ps, n: (bi, 0, 0, 0))
    out = pl.pallas_call(
        kern,
        name="paged_prefill_gqa",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                tile,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, p * bs, rows, r * hd), pool_k.dtype),
                pltpu.VMEM((2, p * bs, rows, r * hd), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((rows, r * cg, 1), jnp.float32),
                pltpu.VMEM((rows, r * cg, 1), jnp.float32),
                pltpu.VMEM((rows, r * cg, r * hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, r * cg, r * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # one row after another: each row's last step prefetches the
            # next row's first blocks
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(past_len, jnp.int32),
      jnp.asarray(lengths, jnp.int32), qd, lane_view(pool_k), lane_view(pool_v))
    # head a of lane row j is the diagonal block (a, a) of the output
    out = out.reshape(b, rows, r, cg, r, hd).diagonal(axis1=2, axis2=4)
    return out.transpose(0, 1, 4, 2, 3).reshape(b, kv, c, g, hd).transpose(
        0, 2, 1, 3, 4)


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
        name="paged_prefill_mla",
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
