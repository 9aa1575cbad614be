"""Unified kernel-backend selection for every kernel family.

One resolution rule, shared by paged attention, the grouped MoE GEMM,
the batched expert GEMV, and flash attention (and any future kernel
package): a config- or call-level *choice* string maps to a concrete
`KernelBackend(kind, interpret)` pair.

    "auto"   -> Pallas kernel on TPU, pure-jnp reference off-TPU
                (interpret mode is far slower than XLA's fused ops on
                CPU, so the kernel path is opt-in there)
    "pallas" -> always the Pallas kernel; interpret mode off-TPU so
                CPU CI still exercises the kernel path
    "ref"    -> always the pure-jnp reference

Config knobs (`cfg.paged_attn_backend`, `cfg.moe_backend`) and the
per-call `backend=` overrides on model entry points
(`gqa/mla_prefill_paged(backend=...)`, `moe_forward(backend=...)`) all
feed this single function, so "which code runs where" has exactly one
answer per choice string.

`KernelBackend` is a NamedTuple, so existing callers that compare
against plain tuples — `resolve_backend("auto") == ("ref", False)` —
keep working unchanged.

Kernel-op wrappers (`moe_gemm.ops`, `expert_gemv.ops`,
`flash_attention.ops`) accept `backend=` and route legacy
`interpret=`/`use_ref=` kwargs through `resolve_op_backend`, which
honors them for one release behind a DeprecationWarning.

Observability: `set_kernel_tracer` installs a process-global
`repro.obs.Tracer`; op wrappers bracket their resolved bodies with
`kernel_span`, annotating the serving timeline with which backend each
kernel family resolved to. See `kernel_span` for the jit staging-time
semantics.
"""
from __future__ import annotations

import warnings
from typing import NamedTuple, Optional

import jax

__all__ = [
    "KernelBackend",
    "kernel_span",
    "resolve_backend",
    "resolve_op_backend",
    "set_kernel_tracer",
]


class KernelBackend(NamedTuple):
    """A resolved backend choice: which implementation, and whether the
    Pallas kernel must run in interpret mode (off-TPU)."""

    kind: str  # "pallas" | "ref"
    interpret: bool


def resolve_backend(choice: str, *, knob: str = "backend") -> KernelBackend:
    """Map a config-level backend choice ("auto" | "pallas" | "ref") to
    a concrete `KernelBackend(kind, interpret)`.

    `knob` only names the config field in the error message, so a typo'd
    `cfg.moe_backend` fails mentioning `moe_backend`, not a generic
    string."""
    on_tpu = jax.default_backend() == "tpu"
    if choice == "auto":
        return KernelBackend("pallas", False) if on_tpu else KernelBackend("ref", False)
    if choice == "pallas":
        return KernelBackend("pallas", not on_tpu)
    assert choice == "ref", f"unknown {knob} {choice!r}"
    return KernelBackend("ref", False)


def resolve_op_backend(
    backend: Optional[str],
    *,
    interpret: Optional[bool] = None,
    use_ref: Optional[bool] = None,
    op: str = "kernel op",
) -> KernelBackend:
    """Backend resolution for kernel-op wrappers that still accept the
    pre-unification `interpret=`/`use_ref=` kwargs.

    `backend=` (a choice string, default "auto") always wins. The legacy
    kwargs are honored for one release when `backend` is not given —
    `use_ref=True` means the jnp oracle, otherwise `interpret` is taken
    as the Pallas interpret flag verbatim (the old contract where the
    caller, not the platform, decided) — and emit a DeprecationWarning
    either way."""
    if interpret is not None or use_ref is not None:
        warnings.warn(
            f"{op}: interpret=/use_ref= are deprecated; pass "
            f'backend="auto"|"pallas"|"ref" instead '
            f"(resolved by repro.kernels.backend.resolve_backend)",
            DeprecationWarning,
            stacklevel=3,
        )
        if backend is None:
            if use_ref:
                return KernelBackend("ref", False)
            return KernelBackend("pallas", bool(interpret))
    return resolve_backend(backend if backend is not None else "auto", knob="backend")


# --------------------------------------------------------------- tracing
# Process-global kernel tracer (like jax.monitoring: one sink). Installed
# by repro.obs.resolve_obs whenever a stack resolves with tracing enabled
# — the LAST enabled stack wins, which is the right answer for the
# one-loop-per-process serving deployments this instrument targets.
_KERNEL_TRACER = None


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def set_kernel_tracer(tracer) -> None:
    """Install (or clear, with None) the process-global tracer that
    `kernel_span` emits to."""
    global _KERNEL_TRACER
    _KERNEL_TRACER = tracer


def kernel_span(op: str, backend: KernelBackend):
    """Span around one resolved kernel invocation at the op-wrapper
    level — records `kernel.<op>` with the resolved (kind, interpret)
    pair on the installed tracer.

    Staging-time semantics: the op wrappers are `jax.jit`-decorated, so
    their Python bodies (and therefore this span) run when a new shape
    TRACES/compiles, not on every device dispatch. On the timeline these
    spans mark compile events and pin down which backend each kernel
    family resolved to; steady-state per-step timing is carried by the
    host-side engine/loop spans, which bracket the dispatched calls."""
    tr = _KERNEL_TRACER
    if tr is None or not tr.enabled:
        return _NULL_SPAN
    return tr.span(
        f"kernel.{op}",
        backend=backend.kind, interpret=bool(backend.interpret),
    )
