"""Grouped expert GEMM Pallas TPU kernel (hot-expert / GPU-domain path).

Tokens arrive pre-sorted by expert and padded so every expert's group is a
multiple of the M-tile (ops.py does this); a scalar-prefetch array maps
each M-tile to its expert id, which the weight BlockSpec index_map uses to
stream the right expert's [D, BN] weight panel into VMEM. Tiles are
MXU-aligned (128); the full-D contraction stays resident per tile:
  x tile  [BM, D]  (bf16, BM=128, D<=8k -> <=2 MB VMEM)
  w panel [D, BN]  (bf16, <=2 MB)
  out     [BM, BN] accumulated in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(tile_expert_ref, x_ref, w_ref, o_ref):
    # tile_expert_ref is scalar-prefetch (consumed by index maps only)
    del tile_expert_ref
    acc = jnp.dot(
        x_ref[...], w_ref[0], preferred_element_type=jnp.float32
    )
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def moe_gemm(
    x: jnp.ndarray,  # [T_pad, D] sorted-by-expert, group-aligned to bm
    w: jnp.ndarray,  # [E, D, F]
    tile_expert: jnp.ndarray,  # [T_pad // bm] int32 expert id per M-tile
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    t, d = x.shape
    e, _, f = w.shape
    assert t % bm == 0 and f % bn == 0, (t, bm, f, bn)

    grid = (t // bm, f // bn)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, d), lambda m, n, te: (m, 0)),
                pl.BlockSpec((1, d, bn), lambda m, n, te: (te[m], 0, n)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, te: (m, n)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(tile_expert, x, w)
