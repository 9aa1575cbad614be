"""Fused few-token expert FFN Pallas kernel — the DIMM-NDP "GEMV & Act
Unit" analogue on TPU.

The paper's NDP unit streams an expert's weights past a tiny activation
set exactly once (256 multipliers + SiLU unit, 256 KB buffer). The TPU
adaptation: grid over F-tiles; each step streams one [D, BF] panel of
W1/W3 and the matching [BF, D] panel of W2 through VMEM, computes
h = silu(x W1_f) * (x W3_f) for the resident token block, and accumulates
h @ W2_f into a VMEM fp32 accumulator. Weights are read from HBM exactly
once (bandwidth-optimal — the cold-expert regime is weight-read bound),
activations stay resident (the 256 KB buffer analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref):
    f_idx = pl.program_id(0)

    @pl.when(f_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    g = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    acc_ref[...] += jnp.dot(h, w2_ref[...], preferred_element_type=jnp.float32)

    @pl.when(f_idx == pl.num_programs(0) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bf", "interpret"))
def expert_ffn_gemv(
    x: jnp.ndarray,  # [T, D] few tokens (cold-expert load)
    w1: jnp.ndarray,  # [D, F]
    w3: jnp.ndarray,  # [D, F]
    w2: jnp.ndarray,  # [F, D]
    *,
    bf: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    t, d = x.shape
    f = w1.shape[1]
    bf = min(bf, f)
    assert f % bf == 0, (f, bf)
    grid = (f // bf,)

    return pl.pallas_call(
        _kernel,
        name="expert_gemv",
        grid=grid,
        in_specs=[
            pl.BlockSpec((t, d), lambda i: (0, 0)),  # tokens resident
            pl.BlockSpec((d, bf), lambda i: (0, i)),  # stream W1 panel
            pl.BlockSpec((d, bf), lambda i: (0, i)),  # stream W3 panel
            pl.BlockSpec((bf, d), lambda i: (i, 0)),  # stream W2 panel
        ],
        out_specs=pl.BlockSpec((t, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x, w1, w3, w2)
