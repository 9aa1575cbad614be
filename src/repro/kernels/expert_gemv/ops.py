"""jit'd wrapper: batched cold-expert execution (one NDP per expert).

Backend selection is the shared `kernels/backend.py` rule: pass
`backend="auto" | "pallas" | "ref"`; the legacy `interpret=`/`use_ref=`
kwargs are honored for one release behind a DeprecationWarning.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.backend import KernelBackend, kernel_span, resolve_op_backend
from repro.kernels.expert_gemv.expert_gemv import expert_ffn_gemv
from repro.kernels.expert_gemv.ref import expert_ffn_ref


@functools.partial(
    jax.jit, static_argnames=("bf", "backend", "interpret", "use_ref")
)
def cold_expert_ffn(
    x: jnp.ndarray,  # [E, C, D] per-expert token buffers (C small)
    w1: jnp.ndarray,  # [E, D, F]
    w3: jnp.ndarray,  # [E, D, F]
    w2: jnp.ndarray,  # [E, F, D]
    *,
    bf: Optional[int] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,  # deprecated: use backend=
    use_ref: Optional[bool] = None,  # deprecated: use backend=
) -> jnp.ndarray:
    """Each expert's buffer runs the fused single-pass FFN — the
    per-DIMM-NDP parallelism of the paper (one localized expert per unit).

    `bf` (a divisor of F) overrides the kernel's F tile, which by
    default divides F (`expert_gemv.f_tile`): the weights go to the
    kernel as they are, never padded or copied."""
    kind, interp = resolve_op_backend(
        backend, interpret=interpret, use_ref=use_ref, op="cold_expert_ffn"
    )
    with kernel_span("cold_expert_ffn", KernelBackend(kind, interp)):
        if kind == "ref":
            return jax.vmap(expert_ffn_ref)(x, w1, w3, w2)
        fn = functools.partial(expert_ffn_gemv, bf=bf, interpret=interp)
        return jax.vmap(fn)(x, w1, w3, w2)
