"""Blockwise online-softmax (flash) attention Pallas TPU kernel.

Used by the 32k-prefill path: the [Sq, Sk] score matrix never leaves
VMEM tiles. Grid (batch*heads, Sq/BQ, Sk/BK); the KV axis is the
innermost ("arbitrary") dimension carrying running max / denominator /
accumulator scratch across iterations. Causal tiles beyond the diagonal
are skipped via pl.when on block indices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, causal, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sq = pl.num_programs(1) * bq
    sk = pl.num_programs(2) * bk
    run = True
    if causal:
        # query block rows [qi*bq, ...) attend key cols <= row + (sk - sq)
        run = ki * bk <= qi * bq + (bq - 1) + (sk - sq)

    @pl.when(run)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        s *= q.shape[-1] ** -0.5
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos + (sk - sq), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(
    q: jnp.ndarray,  # [BH, Sq, dh]
    k: jnp.ndarray,  # [BH, Sk, dh]
    v: jnp.ndarray,  # [BH, Sk, dh]
    *,
    causal: bool = True,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    bh, sq, dh = q.shape
    sk = k.shape[1]
    bq, bk = min(bq, sq), min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0

    grid = (bh, sq // bq, sk // bk)
    kern = functools.partial(_kernel, causal=causal, bq=bq, bk=bk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
