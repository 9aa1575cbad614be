"""Tiered MoE execution — the TPU-native TriMoE runtime (DESIGN.md §2.2).

Expert weights live in three buffers whose *sharding* realizes the
paper's three compute domains:

  hot   [n_hot,  3, D, F]  replicated            (GPU-HBM-resident tier:
                                                  zero collective traffic)
  warm  [n_warm, 3, D, F]  striped over `model`  (AMX-CPU tier: every chip
                                                  cooperates, reduce over ICI
                                                  amortized by token count)
  cold  [n_cold, 3, D, F]  localized over the    (DIMM-NDP tier: tokens
                           full mesh (expert dim) travel to the expert,
                                                  weights never move)

Routing tables (expert_tier[E], expert_slot[E]) are step inputs produced
by the host-side scheduler; migrations between steps move experts across
buffers with resharding collectives — the DIMM-Link relayout analogue.

Under expert parallelism a chip holds a share of each layer's experts
(`MoEConfig.expert_offset`, `n_experts`) and routes over all
`router_width`: the tables keep one entry per router output, and an
expert held on another chip has tier `AWAY_T`, which no tier buffer
serves, so its pairs are never dispatched, counted in a tier, or
migrated here. On one chip nothing stands in for the others.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.hardware import TPU_V5E, tpu_spec
from repro.models.layers import Params, dense_init
from repro.models.moe import expert_ffn, moe_backend, route, shared_ffn

HOT_T, WARM_T, COLD_T = 0, 1, 2
AWAY_T = 3  # held on another chip: no buffer here
TIER_KEYS = ("hot", "warm", "cold")


def tier_occupancy(tiers, ema=None) -> Dict[str, float]:
    """Host-side tier-timeline sample for the observability channel
    (repro.obs): per-tier expert counts aggregated over every MoE layer
    from a [L, E] (or [E]) tier array — the predictor's `decided` grid
    or a layer's `expert_tier` table — plus, when the predictor's [L, E]
    EMA is given, the predicted load mass currently sitting in each
    tier. Emitted as Perfetto counter tracks at every replan, so
    relayout decisions are visually auditable against skew-phase
    shifts."""
    t = np.asarray(tiers)
    out: Dict[str, float] = {}
    for tid, key in enumerate(TIER_KEYS):
        mask = t == tid
        out[f"{key}_experts"] = int(mask.sum())
        if ema is not None:
            out[f"{key}_load"] = float(np.asarray(ema)[mask].sum())
    return out


class TierSizes(NamedTuple):
    n_hot: int
    n_warm: int
    n_cold: int


def validate_tier_sizes(cfg, sizes: TierSizes) -> TierSizes:
    """Reject impossible tier splits before any buffer is allocated.

    The failure this guards: n_hot + n_warm > n_experts leaves a
    negative cold tier, which used to surface only later as a bogus
    buffer shape deep inside init/dispatch."""
    n_hot, n_warm, n_cold = sizes
    e = cfg.moe.n_experts  # the experts held here
    if n_hot < 1 or n_warm < 0 or n_cold < 0:
        raise ValueError(
            f"invalid tier sizes {tuple(sizes)}: need n_hot >= 1 and "
            f"non-negative warm/cold"
        )
    if n_hot + n_warm > e:
        raise ValueError(
            f"impossible tier split: n_hot + n_warm = {n_hot + n_warm} "
            f"exceeds n_experts = {e}"
        )
    if n_hot + n_warm + n_cold != e:
        raise ValueError(
            f"tier sizes {tuple(sizes)} sum to {n_hot + n_warm + n_cold}, "
            f"expected n_experts = {e}"
        )
    return sizes


def tier_sizes(cfg, n_chips: Optional[int] = None, hbm_budget_frac: float = 0.15,
               reclaimed_kv_bytes: int = 0) -> TierSizes:
    """Size the tiers so the replicated hot buffer fits its HBM budget and
    warm stays affordable when striped over the model axis; everything
    else is cold (localized). Mirrors the paper's HBM-capacity-driven hot
    set with the DIMM pool as the elastic tail.

    `n_chips` is the mesh size the warm stripe and cold (localized)
    shards spread over; None reads the actual device count from the
    live JAX mesh instead of assuming a fictional pod. The hot tier is
    replicated, so its HBM budget is per-chip and independent of
    `n_chips` — sizing is mesh-stable, but the split is validated
    against the real mesh (a warm stripe needs at least one chip).

    `reclaimed_kv_bytes` is HBM handed back by the KV layer (the paged
    cache's pool savings vs a contiguous per-slot reservation,
    serving/paged_kv.py) — it joins the hot budget directly, so prefix
    reuse translates into more HBM-resident hot experts (paper §3.1:
    the hot set is HBM-budget-driven)."""
    if n_chips is None:
        n_chips = jax.device_count()
    if n_chips < 1:
        raise ValueError(f"n_chips must be >= 1, got {n_chips}")
    mo = cfg.moe
    w_bytes = 3 * cfg.d_model * mo.d_expert * 2
    n_moe_layers = max(1, sum(cfg.uses_moe_layer(i) for i in range(cfg.n_layers)))
    budget = _hbm_bytes() * hbm_budget_frac + max(0, reclaimed_kv_bytes)
    n_hot = max(1, min(mo.n_experts // 4, int(budget / (w_bytes * n_moe_layers))))
    n_warm = max(1, min(mo.n_experts - n_hot - 1, int(round(0.30 * mo.n_experts))))
    n_cold = mo.n_experts - n_hot - n_warm
    return validate_tier_sizes(cfg, TierSizes(n_hot, n_warm, n_cold))


def _hbm_bytes() -> float:
    """HBM of the chip serving runs on. On a TPU it comes from the
    `device_kind` table (an unknown kind raises); off-TPU (CPU tests)
    there is no HBM to read, so the v5e constant stands in."""
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return tpu_spec(dev.device_kind).hbm_bytes
    return TPU_V5E.hbm_bytes


def init_tiered_state(rng, cfg, sizes: TierSizes, pad_cold_to: int = 16) -> Params:
    """Tier buffers + routing tables for one MoE layer.

    Initial assignment of the held experts, in global-id order: the
    first n_hot hot, the next n_warm warm, the rest cold — the host
    engine re-ranks by offline trace analysis before serving and
    migrates thereafter. The tables have one entry per router output;
    experts held elsewhere are `AWAY_T`. The cold buffer is padded to
    a multiple of the mesh's data axis so the localized (expert-sharded)
    layout always divides.
    """
    mo = cfg.moe
    d, f = cfg.d_model, mo.d_expert
    dt = jnp.dtype(cfg.param_dtype)
    validate_tier_sizes(cfg, TierSizes(*sizes))
    ks = jax.random.split(rng, 3)

    def buf(key, n):  # a tier may be empty (a share of 2 experts: 1/1/0)
        return dense_init(key, (n, 3, d, f), dt) if n else jnp.zeros((0, 3, d, f), dt)

    n_hot, n_warm, n_cold = sizes
    n_cold_slots = -(-n_cold // pad_cold_to) * pad_cold_to
    tier = jnp.concatenate(
        [
            jnp.full((n_hot,), HOT_T, jnp.int32),
            jnp.full((n_warm,), WARM_T, jnp.int32),
            jnp.full((n_cold,), COLD_T, jnp.int32),
        ]
    )
    slot = jnp.concatenate(
        [
            jnp.arange(n_hot, dtype=jnp.int32),
            jnp.arange(n_warm, dtype=jnp.int32),
            jnp.arange(n_cold, dtype=jnp.int32),
        ]
    )
    if mo.is_share:
        away = mo.router_width - mo.n_experts - mo.expert_offset
        tier = jnp.pad(tier, (mo.expert_offset, away), constant_values=AWAY_T)
        slot = jnp.pad(slot, (mo.expert_offset, away))
    return {
        "hot": buf(ks[0], n_hot),
        "warm": buf(ks[1], n_warm),
        "cold": buf(ks[2], n_cold_slots),
        "expert_tier": tier,
        "expert_slot": slot,
    }


def tier_capacity(tid: int, t: int, top_k: int, cold_capacity_frac: float) -> int:
    """Rows per buffer slot of tier `tid` in a step over `t` tokens: hot
    and warm serve any skew droplessly; cold buffers run at the
    invariant-backed reduced capacity."""
    if tid != COLD_T:
        return t
    return max(top_k, int(t * cold_capacity_frac + 0.999))


def _tier_ffn(w: jnp.ndarray, h: jnp.ndarray, kind: str = "ref",
              decode: bool = False) -> jnp.ndarray:
    """w: [n, 3, D, F]; h: [n, C, D] -> [n, C, D], routed by the
    resolved `cfg.moe_backend` kind: the Pallas grouped GEMM / batched
    GEMV kernels or the grouped einsums (models/moe.expert_ffn)."""
    return expert_ffn(h, w[:, 0], w[:, 1], w[:, 2].transpose(0, 2, 1),
                      kind=kind, decode=decode)


def _dispatch_tier(flat, st, sw, tier_slot, in_tier, n_slots, cap):
    """Scatter this tier's assignments into [n_slots, cap, D] buffers."""
    t, d = flat.shape[0], flat.shape[1]
    # rank within (tier, slot): count prior occurrences via sorted trick
    key = jnp.where(in_tier, tier_slot, n_slots)
    order = jnp.argsort(key, stable=True)
    ks = key[order]
    pos_sorted = jnp.arange(len(ks), dtype=jnp.int32) - jnp.searchsorted(
        ks, ks, side="left"
    ).astype(jnp.int32)
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)
    ok = in_tier & (pos < cap)
    dst = jnp.where(ok, key * cap + pos, n_slots * cap)
    buf = jnp.zeros((n_slots * cap + 1, d), flat.dtype).at[dst].set(flat[st])
    return buf[: n_slots * cap].reshape(n_slots, cap, d), dst, ok


def tiered_moe_forward(
    p: Params,  # model params for this layer's ffn: router (+ shared)
    state: Params,  # tier buffers + routing tables
    cfg,
    x: jnp.ndarray,  # [B, S, D] (decode: S == 1)
    cold_capacity_frac: float = 0.25,
    token_mask: jnp.ndarray | None = None,  # [B, S] or [B*S] bool
    backend: str | None = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, expert_counts[router_width]): pairs of experts held
    on other chips are counted but not dispatched (their tier is
    `AWAY_T`); the shared experts run on every token.

    cold_capacity_frac (§Perf): cold experts are low-load by scheduling
    invariant (relayout re-stripes anything above tau_cold), so their
    dispatch buffers run at a fraction of the dropless capacity; 1.0
    restores exact dropless behavior.

    token_mask: invalid tokens (dead batch slots padded into a fixed-
    width zigzag group) are excluded from dispatch and from the expert
    counts, so the load predictor never sees phantom routing.

    backend: per-call override of `cfg.moe_backend` — each tier's FFN
    runs the Pallas kernels (decode steps the batched GEMV, prefill the
    fused grouped GEMM) or the einsum reference; dispatch/combine and
    the migration machinery are backend-invariant."""
    mo = cfg.moe
    k = mo.top_k
    b, s, d = x.shape
    kind, _ = moe_backend(cfg, backend)
    t = b * s
    flat = x.reshape(t, d)

    _, w, idx = route(p, mo, flat)

    a_tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    a_exp = idx.reshape(-1).astype(jnp.int32)
    a_w = w.reshape(-1)
    a_live = None
    if token_mask is not None:
        a_live = jnp.repeat(token_mask.reshape(t), k)

    a_tier = state["expert_tier"][a_exp]
    a_slot = state["expert_slot"][a_exp]

    y = jnp.zeros((t, d), x.dtype)
    for tid, key in enumerate(TIER_KEYS):
        n_slots = state[key].shape[0]
        cap = tier_capacity(tid, t, mo.top_k, cold_capacity_frac)
        in_tier = a_tier == tid
        if a_live is not None:
            in_tier = in_tier & a_live
        h, dst, ok = _dispatch_tier(
            flat, a_tok, a_w, a_slot, in_tier, n_slots, cap
        )
        o = _tier_ffn(state[key], h, kind=kind, decode=(s == 1))
        obuf = jnp.concatenate(
            [o.reshape(n_slots * cap, d), jnp.zeros((1, d), o.dtype)]
        )
        contrib = obuf[dst] * (a_w * ok)[:, None].astype(o.dtype)
        y = y.at[a_tok].add(contrib)

    y = y.reshape(b, s, d)
    if mo.n_shared:
        y = y + shared_ffn(p["shared"], x)
    one = 1 if a_live is None else a_live.astype(jnp.int32)
    counts = jnp.zeros((mo.router_width,), jnp.int32).at[a_exp].add(one)
    return y, counts


# ------------------------------------------------------------ migrations
def apply_migrations(state: Params, plan: jnp.ndarray, g=None) -> Params:
    """Execute a fixed-size migration plan (padded with no-ops).

    plan: [M, 5] int32 rows (expert_a, tier_a, slot_a, tier_b, slot_b):
    swap the weights living at (tier_a, slot_a) and (tier_b, slot_b) and
    update the routing tables for the two experts involved. A row with
    expert_a < 0 is a no-op. On hardware each swap lowers to resharding
    collectives between differently-sharded buffers — the DIMM-Link
    relayout/rebalance analogue, overlapped with the next step's compute.

    `state` is one layer's tier state, or with `g` a stack of layer
    groups ([G, n, 3, D, F] buffers, [G, E] tables) of which group `g`
    (traced) migrates. A swap reads and writes only the slot windows it
    touches: each tier buffer takes one `dynamic_update_slice` per side
    of the swap, of the swapped expert or of the slot's own value, so
    no row branches over whole buffers and, jitted with the state
    donated, XLA updates the buffers in place. Rows run up to the last
    live one; no-op rows before it write back what they read.
    """
    if g is None:
        one = jax.tree.map(lambda a: a[None], state)
        return jax.tree.map(lambda a: a[0], apply_migrations(one, plan, 0))
    bufs = [state[k] for k in TIER_KEYS]
    window = (1, 1) + bufs[0].shape[2:]
    zeros = (0,) * (len(window) - 2)

    def start(buf, slot):
        return (g, jnp.clip(slot, 0, buf.shape[1] - 1)) + zeros

    def pick(tid, xs):
        return jnp.where(tid == 0, xs[0], jnp.where(tid == 1, xs[1], xs[2]))

    def one(i, carry):
        bufs, tier_g, slot_g = carry
        ea, ta, sa, tb, sb = plan[i, 0], plan[i, 1], plan[i, 2], plan[i, 3], plan[i, 4]
        live = ea >= 0
        at_a = [jax.lax.dynamic_slice(b, start(b, sa), window) for b in bufs]
        at_b = [jax.lax.dynamic_slice(b, start(b, sb), window) for b in bufs]
        # every read lands before any write, or XLA copies the buffers
        bufs, at_a, at_b = jax.lax.optimization_barrier((bufs, at_a, at_b))
        wa, wb = pick(ta, at_a), pick(tb, at_b)
        new_bufs = []
        for tid, buf in enumerate(bufs):
            xa = jnp.where(live & (ta == tid), wb, at_a[tid])
            buf = jax.lax.dynamic_update_slice(buf, xa, start(buf, sa))
            # side b's window, after side a's write if they coincide
            same = start(buf, sa)[1] == start(buf, sb)[1]
            xb = jnp.where(live & (tb == tid), wa, jnp.where(same, xa, at_b[tid]))
            new_bufs.append(jax.lax.dynamic_update_slice(buf, xb, start(buf, sb)))
        # table update: expert at (tb, sb) before the swap moves to (ta, sa)
        occupant_b = jnp.argmax((tier_g == tb) & (slot_g == sb)).astype(jnp.int32)
        tier = tier_g.at[ea].set(tb).at[occupant_b].set(ta)
        slot = slot_g.at[ea].set(sb).at[occupant_b].set(sa)
        return (new_bufs, jnp.where(live, tier, tier_g),
                jnp.where(live, slot, slot_g))

    rows = jnp.arange(1, plan.shape[0] + 1, dtype=jnp.int32)
    n = jnp.max(jnp.where(plan[:, 0] >= 0, rows, 0))
    tables = [jax.lax.dynamic_index_in_dim(state[f], g, 0, keepdims=False)
              for f in ("expert_tier", "expert_slot")]
    bufs, tier_g, slot_g = jax.lax.fori_loop(0, n, one, (bufs, *tables))
    out = dict(state, **dict(zip(TIER_KEYS, bufs)))
    for f, t in (("expert_tier", tier_g), ("expert_slot", slot_g)):
        out[f] = jax.lax.dynamic_update_index_in_dim(state[f], t, g, 0)
    return out
