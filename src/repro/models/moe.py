"""Routed MoE layer (training/prefill path) with sort-based dispatch.

Dispatch is scatter/gather (argsort by expert id -> capacity-bounded
expert buffers -> grouped FFN -> weighted combine), NOT one-hot einsum:
for E=160 experts a one-hot dispatch matmul would add ~1000x the useful
FLOPs and poison the roofline.

The expert FFN over the dispatched buffers routes through the shared
kernel-backend API (`cfg.moe_backend`, kernels/backend.py): when it
resolves to "pallas", prefill-shaped buffers run the fused grouped MoE
GEMM (`kernels/moe_gemm.grouped_expert_ffn`, MXU-aligned tiles, one
wide gate+up GEMM) and decode-shaped buffers (S == 1, small capacity)
run the batched expert GEMV (`kernels/expert_gemv.cold_expert_ffn`,
weights streamed past the resident tokens exactly once); "ref" keeps
the inline grouped einsums. `moe_forward(backend=...)` overrides the
config per call, mirroring `gqa/mla_prefill_paged(backend=...)`.

Returns per-expert token counts alongside the output — the load signal
the TriMoE predictor/scheduler (core/) consumes.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.expert_gemv import cold_expert_ffn
from repro.kernels.moe_gemm import grouped_expert_ffn
from repro.kernels.moe_gemm.ref import grouped_ffn_ref
from repro.models.layers import Params, dense_init


class MoEOutput(NamedTuple):
    y: jnp.ndarray  # [B, S, D]
    aux_loss: jnp.ndarray  # scalar load-balance loss
    expert_counts: jnp.ndarray  # [router_width] int32 tokens per expert


# --- sharding hints for the grouped dispatch path (§Perf) -------------
# GSPMD left alone all-gathers the [B, E, C, D] dispatch buffers across
# the expert axis; pinning them to (data, model) turns the dispatch into
# the intended all-to-all. Set by launch/dryrun.py (and real launchers)
# when a mesh is active; None = no constraints (single device).
_SHARDING_HINTS = None  # (dp_axes, ep_axis) | None


def set_moe_sharding_hints(dp=("data",), ep="model", enable=True):
    global _SHARDING_HINTS
    _SHARDING_HINTS = ((dp if isinstance(dp, tuple) else (dp,)), ep) if enable else None


def _hint(arr, *spec):
    if _SHARDING_HINTS is None:
        return arr
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(arr, P(*spec))


def init_moe(rng, cfg) -> Params:
    """Router over all `router_width` experts; weights of the `n_experts`
    held here (a chip's share under expert parallelism, else all)."""
    mo = cfg.moe
    d, f, e = cfg.d_model, mo.d_expert, mo.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], (d, mo.router_width), jnp.float32),
        "w_gate": dense_init(ks[1], (e, d, f), dt),
        "w_up": dense_init(ks[2], (e, d, f), dt),
        "w_down": dense_init(ks[3], (e, f, d), dt),
    }
    if mo.selection_bias:  # learned; zero at init, as in GLM-4.5
        p["router_bias"] = jnp.zeros((mo.router_width,), jnp.float32)
    if mo.n_shared:
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(sk[0], (mo.n_shared, d, f), dt),
            "w_up": dense_init(sk[1], (mo.n_shared, d, f), dt),
            "w_down": dense_init(sk[2], (mo.n_shared, f, d), dt),
        }
    return p


def router_topk(logits: jnp.ndarray, mo, bias=None):
    """Scores, gates and chosen experts of float32 router logits [T, E].

    softmax: top-k of the probabilities (DeepSeek-V2, granite); sigmoid:
    independent scores (GLM-4.5). A selection `bias` [E] moves which
    experts are chosen, never the gates: those are the unbiased scores
    of the chosen experts, divided by their sum. Returns (scores [T, E],
    gates [T, k], global expert ids [T, k])."""
    logits = logits.astype(jnp.float32)
    if mo.scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif mo.scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"unknown router scoring {mo.scoring!r}")
    if bias is None:
        w, idx = jax.lax.top_k(scores, mo.top_k)
    else:
        _, idx = jax.lax.top_k(scores + bias, mo.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return scores, w, idx


def route(p: Params, mo, x: jnp.ndarray):
    """`router_topk` of tokens x [..., D] through the layer's router
    [D, router_width] (and selection bias, when the layer has one)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"])
    return router_topk(logits, mo, p.get("router_bias"))


def dispatch_key(a_exp: jnp.ndarray, mo, live=None) -> jnp.ndarray:
    """Sort key of (token, expert) pairs with global expert ids `a_exp`:
    the held expert's local id, or `n_experts` (sorts past every held
    expert and is never dispatched) for an expert held on another chip
    or, where `live` is False, a pad token."""
    e = mo.n_experts
    key = a_exp
    if mo.is_share:
        key = a_exp - mo.expert_offset
        key = jnp.where((key >= 0) & (key < e), key, e)
    return key if live is None else jnp.where(live, key, e)


def moe_backend(cfg, backend: str | None = None):
    """Resolve the expert-FFN backend: an explicit `backend` overrides
    `cfg.moe_backend` through the shared kernels/backend.py rule ("auto"
    = Pallas kernels on TPU, grouped einsums elsewhere; "pallas" forces
    the kernels, interpret mode off-TPU, so CPU CI exercises the kernel
    path; "ref" forces the einsums)."""
    from repro.kernels.backend import resolve_backend

    return resolve_backend(
        backend or getattr(cfg, "moe_backend", "auto"), knob="moe_backend"
    )


def grouped_ffn(h: jnp.ndarray, w_gate, w_up, w_down) -> jnp.ndarray:
    """h: [E, C, D] expert buffers -> [E, C, D]: the einsum reference
    (kernels/moe_gemm's oracle, shared so kernel parity is structural)."""
    return grouped_ffn_ref(h, w_gate, w_up, w_down)


def expert_ffn(h: jnp.ndarray, w_gate, w_up, w_down, *, kind: str = "ref",
               decode: bool = False, group_expert=None) -> jnp.ndarray:
    """Expert FFN over dispatched buffers h [G, C, D], routed by the
    resolved backend `kind`:

      ref    -> the grouped einsums (XLA; the kernels' shared oracle)
      pallas -> decode buffers (S == 1 dispatch, C small, weight-read
                bound) hit the batched expert GEMV; everything else the
                fused grouped MoE GEMM (MXU-aligned tiles).

    `group_expert` maps buffer groups to expert weight rows when G != E
    (the per-row dispatch's [B*E] groups)."""
    if kind != "pallas":
        return grouped_ffn_ref(h, w_gate, w_up, w_down, group_expert)
    if decode and group_expert is None:
        return cold_expert_ffn(h, w_gate, w_up, w_down, backend="pallas")
    return grouped_expert_ffn(h, w_gate, w_up, w_down, group_expert,
                              backend="pallas")


def shared_ffn(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    g = jnp.einsum("bsd,edf->bsef", x, p["w_gate"])
    u = jnp.einsum("bsd,edf->bsef", x, p["w_up"])
    a = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("bsef,efd->bsd", a, p["w_down"])


def moe_forward(
    p: Params, cfg, x: jnp.ndarray, *, capacity_factor=None, full_capacity=False,
    grouped: bool | None = None, token_mask=None, backend: str | None = None,
) -> MoEOutput:
    """Routed MoE. Two dispatch strategies:

    grouped (default for full sequences): tokens sort PER BATCH ROW, so
      with rows sharded over `data` every argsort/searchsorted is
      device-local and the only cross-device traffic is the expert
      all-to-all of [B, E, C, D] buffers — the §Perf fix for the
      distributed-sort-network collectives of the global path.
    global (decode / tiny batches): one flat sort with per-expert
      capacity = t (dropless).

    `token_mask` [B, S] bool (bucketed masked prefill / dead decode
    slots): masked tokens are excluded from dispatch, counts, and the
    aux loss, so padding never displaces real tokens or pollutes the
    load signal. Supported on both paths; on the grouped path masked
    assignments take a sentinel expert id so the row-local sort parks
    them past every real assignment (bucketed prefill under sharded
    all-to-all dispatch).

    `backend` overrides `cfg.moe_backend` for this call (see
    `moe_backend()`); dispatch/combine are backend-invariant, only the
    expert FFN over the dispatched buffers switches implementation.
    """
    mo = cfg.moe
    b, s, d = x.shape
    kind, _ = moe_backend(cfg, backend)
    if grouped is None:
        # dispatch-strategy trade-off (§Perf, re-measured under the kernel
        # path): the Pallas backend equalizes the expert-FFN compute shape
        # between strategies (both feed the same grouped GEMM tiles), but
        # GSPMD still lowers the grouped path's [B, E, C, D] buffer
        # exchange as all-gathers (+24% collective bytes), so the global
        # path remains the default until the shard_map all-to-all variant
        # lands. Revisit the default with that variant, not the backend.
        grouped = False
    if grouped:
        return _moe_forward_grouped(p, cfg, x, capacity_factor, full_capacity,
                                    token_mask, kind=kind)
    return _moe_forward_global(p, cfg, x, capacity_factor, full_capacity,
                               token_mask, kind=kind)


def _moe_forward_global(p, cfg, x, capacity_factor, full_capacity,
                        token_mask=None, kind: str = "ref") -> MoEOutput:
    mo = cfg.moe
    e, k = mo.n_experts, mo.top_k
    b, s, d = x.shape
    t = b * s
    if full_capacity:
        cap = t  # droplessly serve any skew (decode: t = batch, small)
    else:
        cf = capacity_factor if capacity_factor is not None else mo.capacity_factor
        cap = min(t, max(k, int(t * k * cf / e + 0.5)))

    flat = x.reshape(t, d)
    probs, w, idx = route(p, mo, flat)
    live = None if token_mask is None else token_mask.reshape(t)

    # --- flatten (token, expert) assignments and sort by expert ---
    a_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    a_exp = idx.reshape(-1).astype(jnp.int32)
    a_w = w.reshape(-1)
    # pad assignments and experts held elsewhere get the sentinel key e:
    # they sort past every held expert, so they never claim capacity
    a_key = dispatch_key(a_exp, mo, None if live is None else jnp.repeat(live, k))
    order = jnp.argsort(a_key, stable=True)
    se, st, sw = a_key[order], a_tok[order], a_w[order]
    # rank within expert group (se is sorted)
    pos = jnp.arange(t * k, dtype=jnp.int32) - jnp.searchsorted(
        se, se, side="left"
    ).astype(jnp.int32)
    keep = (pos < cap) & (se < e)
    slot = jnp.where(keep, se * cap + pos, e * cap)  # overflow row dropped

    # --- dispatch: scatter into [E*cap(+1), D] buffers ---
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(flat[st])
    h = buf[: e * cap].reshape(e, cap, d)
    # decode steps (S == 1) are the small-capacity weight-read-bound
    # regime the batched GEMV targets; everything else is GEMM-shaped
    o = expert_ffn(h, p["w_gate"], p["w_up"], p["w_down"], kind=kind,
                   decode=(s == 1))
    obuf = jnp.concatenate([o.reshape(e * cap, d), jnp.zeros((1, d), o.dtype)])

    # --- combine: gather back + weighted sum over the k assignments ---
    contrib = obuf[slot] * (sw * keep)[:, None].astype(o.dtype)
    y = jnp.zeros((t, d), x.dtype).at[st].add(contrib).reshape(b, s, d)

    if mo.n_shared:
        y = y + shared_ffn(p["shared"], x)

    # --- load-balance aux loss (Switch-style) + expert load counts,
    # over every router output
    er = mo.router_width
    if live is None:
        counts = jnp.zeros((er,), jnp.int32).at[a_exp].add(1)
        frac_tokens = counts.astype(jnp.float32) / (t * k)
        frac_probs = probs.mean(0)
    else:
        counts = jnp.zeros((er,), jnp.int32).at[a_exp].add(
            jnp.repeat(live, k).astype(jnp.int32)
        )
        n_live = jnp.maximum(live.sum().astype(jnp.float32), 1.0)
        frac_tokens = counts.astype(jnp.float32) / (n_live * k)
        frac_probs = (probs * live[:, None]).sum(0) / n_live
    aux = mo.router_aux_coef * er * jnp.sum(frac_tokens * frac_probs)
    return MoEOutput(y, aux, counts)


def _moe_forward_grouped(p, cfg, x, capacity_factor, full_capacity=False,
                         token_mask=None, kind: str = "ref") -> MoEOutput:
    """Per-row dispatch: [B, S, D] -> buffers [B, E, C, D] -> expert FFN
    -> combine. All sorting is row-local; sharding B over `data` and E
    over `model` makes the dispatch one all-to-all.

    `token_mask` [B, S]: masked assignments get the sentinel expert id
    `e`, so the stable row-local sort parks them after every real
    assignment — they can never claim capacity, and real tokens' ranks
    (hence buffer slots and outputs) are identical to an unpadded
    dispatch of the row's real prefix (tests/test_moe.py)."""
    mo = cfg.moe
    e, k = mo.n_experts, mo.top_k
    b, s, d = x.shape
    if full_capacity:
        cap = s  # dropless: masked prefill must not tie capacity to pads
    else:
        cf = capacity_factor if capacity_factor is not None else mo.capacity_factor
        cap = min(s, max(k, int(s * k * cf / e + 0.5)))

    # NOTE (§Perf, refuted iteration): forcing x to data-only sharding here
    # replicates activations across the model axis every MoE layer and its
    # gradient all-reduces cost 18x more collective time than it saves.
    probs, w, idx = route(p, mo, x)  # [B,S,k]

    a_tok = jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32)[None, :, None], (b, s, k)
    ).reshape(b, s * k)
    a_exp = idx.reshape(b, s * k).astype(jnp.int32)
    a_w = w.reshape(b, s * k)
    live = None if token_mask is None else jnp.repeat(token_mask, k, axis=-1)
    a_key = dispatch_key(a_exp, mo, live)

    order = jnp.argsort(a_key, axis=-1, stable=True)  # row-local sort
    se = jnp.take_along_axis(a_key, order, axis=-1)
    st = jnp.take_along_axis(a_tok, order, axis=-1)
    sw = jnp.take_along_axis(a_w, order, axis=-1)
    pos = jnp.arange(s * k, dtype=jnp.int32)[None, :] - jax.vmap(
        lambda row: jnp.searchsorted(row, row, side="left")
    )(se).astype(jnp.int32)
    keep = (pos < cap) & (se < e)
    slot = jnp.where(keep, se * cap + pos, e * cap)  # [B, S*k]

    xk = jnp.take_along_axis(x, st[..., None], axis=1)  # [B, S*k, D]
    buf = jnp.zeros((b, e * cap + 1, d), x.dtype)
    buf = buf.at[jnp.arange(b)[:, None], slot].set(xk)
    h = buf[:, : e * cap].reshape(b, e, cap, d)
    if _SHARDING_HINTS is not None:
        dp, ep = _SHARDING_HINTS
        dpa = dp if len(dp) > 1 else dp[0]
        # rows stay on their data shard; expert dim moves via all-to-all
        h = _hint(h, dpa, ep, None, None)

    # expert FFN over row-grouped buffers (EP all-to-all happens here).
    # The kernel path flattens [B, E, C, D] to B*E groups over the SAME
    # [E, D, F] weights via the fused GEMM's group->expert indirection
    # (tile b copies of arange(E)) — no weight replication, each row's
    # buffers stream the one shared weight panel per expert.
    if kind == "pallas":
        ge = jnp.tile(jnp.arange(e, dtype=jnp.int32), b)
        o = expert_ffn(
            h.reshape(b * e, cap, d), p["w_gate"], p["w_up"], p["w_down"],
            kind=kind, group_expert=ge,
        ).reshape(b, e, cap, d)
    else:
        g = jnp.einsum("becd,edf->becf", h, p["w_gate"])
        u = jnp.einsum("becd,edf->becf", h, p["w_up"])
        a = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * u
        o = jnp.einsum("becf,efd->becd", a, p["w_down"])
    if _SHARDING_HINTS is not None:
        dp, ep = _SHARDING_HINTS
        dpa = dp if len(dp) > 1 else dp[0]
        o = _hint(o, dpa, ep, None, None)

    obuf = jnp.concatenate(
        [o.reshape(b, e * cap, d), jnp.zeros((b, 1, d), o.dtype)], axis=1
    )
    contrib = jnp.take_along_axis(obuf, slot[..., None], axis=1)
    contrib = contrib * (sw * keep)[..., None].astype(o.dtype)
    y = jnp.zeros((b, s, d), x.dtype).at[
        jnp.arange(b)[:, None], st
    ].add(contrib)

    if mo.n_shared:
        y = y + shared_ffn(p["shared"], x)

    er = mo.router_width
    if live is None:
        counts = jnp.zeros((er,), jnp.int32).at[a_exp.reshape(-1)].add(1)
        frac_tokens = counts.astype(jnp.float32) / (b * s * k)
        frac_probs = probs.reshape(-1, er).mean(0)
    else:
        counts = jnp.zeros((er,), jnp.int32).at[a_exp.reshape(-1)].add(
            live.reshape(-1).astype(jnp.int32)
        )
        n_live = jnp.maximum(
            token_mask.sum().astype(jnp.float32), 1.0
        )
        frac_tokens = counts.astype(jnp.float32) / (n_live * k)
        frac_probs = (
            probs.reshape(-1, er) * token_mask.reshape(-1)[:, None]
        ).sum(0) / n_live
    aux = mo.router_aux_coef * er * jnp.sum(frac_tokens * frac_probs)
    return MoEOutput(y, aux, counts)
