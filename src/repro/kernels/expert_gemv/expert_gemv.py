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

The F tile (`f_tile`) is the widest lane-aligned divisor of F whose
double-buffered panels fit the scoped VMEM limit beside the token
block, so no width needs padding: granite's D=1024, F=512 streams one
512-wide panel per expert, GLM-4.5's D=4096, F=1408 (11 x 128) eleven
128-wide ones.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# the TPU compiler's default scoped VMEM limit on v5e (and v4/v6e)
VMEM_LIMIT_BYTES = 16 * 2**20


def f_tile(t: int, d: int, f: int, itemsize: int) -> int:
    """F tile of the GEMV for `t` token rows at widths D, F: the largest
    multiple of 128 dividing F whose double-buffered [D, bf] W1 and W3
    and [bf, D] W2 panels fit the scoped VMEM limit together with the
    double-buffered token and output blocks and the fp32 accumulator.
    F itself when no multiple of 128 divides it (a block spanning the
    whole axis is always legal); the narrowest candidate when none fits,
    for the compiler to refuse."""
    fixed = 4 * t * d * itemsize + 4 * t * d  # x, out (2 buffers each), acc
    cands = [b for b in range(LANE, f + 1, LANE) if f % b == 0] or [f]
    fits = [b for b in cands if fixed + 2 * 3 * d * b * itemsize <= VMEM_LIMIT_BYTES]
    return max(fits) if fits else min(cands)


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
    bf: int | None = None,  # F tile; None = `f_tile`
    interpret: bool = False,
) -> jnp.ndarray:
    t, d = x.shape
    f = w1.shape[1]
    bf = bf or f_tile(t, d, f, w1.dtype.itemsize)
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
