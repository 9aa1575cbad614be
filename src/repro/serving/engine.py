"""TriMoE serving engine: the online loop of paper §4 on the TPU runtime.

Per decode step:
  1. jitted `decode_step(..., tiered=...)` executes attention + the
     three-tier MoE and returns per-expert token counts;
  2. the host updates the EMA predictor (Eq. 8) with the realized loads
     (`observe`);
  3. hysteresis tier decisions are diffed against the current placement,
     candidate migrations are ranked bottleneck-first (moves draining
     the most expensive tier ahead of equal-benefit moves elsewhere —
     §4.2's refinement) by TPU-domain cost benefit
     (core.cost_model.TPUDomains), and the plan is SIZED by the cost
     model: moves are admitted while amortized benefit beats the
     weight-swap cost, clamped to the policy's [plan_min, plan_max]
     (`plan_migrations`);
  4. jitted `apply_migrations` swaps expert weights across tier buffers
     (resharding collectives = DIMM-Link relayout) — `apply_planned` is
     deferred by the serving loop until the *next* step has been
     dispatched, so migration work overlaps the in-flight zigzag group
     (the host-side analogue of double-buffered relayout).

All scheduling knobs come from one `SchedulerPolicy`
(core/policy.py), resolved by `resolve_policy` — the bare `plan_size=`
/ `thresholds=` kwargs are deprecated but honored one release.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cost_model import ExpertShape, TPUDomains
from repro.core.policy import SchedulerPolicy, resolve_policy
from repro.core.predictor import EMALoadPredictor
from repro.core.tiers import COLD, HOT, WARM, TierThresholds
from repro.models.layers import Params
from repro.models.model import (
    decode_step,
    decode_step_paged,
    decode_verify,
    layer_signature,
    prefill,
    prefill_paged,
    stack_plan,
)
from repro.obs import resolve_obs
from repro.obs.metrics import RegistryStats
from repro.serving.kv_cache import SlotKVCache, gather_slots, scatter_slots
from repro.serving.paged_kv import PagedKVCache
from repro.serving.tiered_moe import (
    TierSizes,
    apply_migrations,
    init_tiered_state,
    tier_occupancy,
    tier_sizes,
)

TIER_OF = {HOT: 0, WARM: 1, COLD: 2}


def moe_slot_names(cfg: ModelConfig):
    """Which scan slots (and unrolled layers) carry MoE."""
    unrolled, n_groups, period = stack_plan(cfg)
    slots = [f"slot{j}" for j, sig in enumerate(period) if sig[1] == "moe"]
    layers = [f"layer{li}" for li in unrolled if layer_signature(cfg, li)[1] == "moe"]
    return layers, slots, n_groups


def init_tiered_for_model(rng, cfg: ModelConfig, sizes: Optional[TierSizes] = None) -> Params:
    """Tiered states mirroring the params stacking (scan groups x slots)."""
    if cfg.moe is None:
        return None
    sizes = sizes or tier_sizes(cfg)
    layers, slots, n_groups = moe_slot_names(cfg)
    out: Params = {}
    for name in layers:
        rng, k = jax.random.split(rng)
        out[name] = init_tiered_state(k, cfg, sizes)
    if slots:
        def one_group(key):
            ks = jax.random.split(key, len(slots))
            return {s: init_tiered_state(ks[i], cfg, sizes) for i, s in enumerate(slots)}

        rng, k = jax.random.split(rng)
        out["stack"] = jax.vmap(one_group)(jax.random.split(k, n_groups))
    return out


def fill_tiers_from_params(params: Params, tiered: Params, cfg: ModelConfig) -> Params:
    """Copy the flat MoE expert weights into tier buffers according to the
    routing tables, so tiered serving is numerically identical to the
    trained model. Works on real arrays (smoke/examples scale)."""
    layers, slots, n_groups = moe_slot_names(cfg)

    def fill_one(state, w_gate, w_up, w_down):
        wstack = jnp.stack([w_gate, w_up, w_down.transpose(0, 2, 1)], axis=1)
        new = dict(state)
        tier = np.asarray(state["expert_tier"])
        slot = np.asarray(state["expert_slot"])
        for tid, key in enumerate(("hot", "warm", "cold")):
            buf = np.asarray(state[key]).copy()
            for e in np.nonzero(tier == tid)[0]:
                buf[slot[e]] = np.asarray(wstack[e])
            new[key] = jnp.asarray(buf)
        return new

    out = dict(tiered)
    for name in layers:
        ffn = params[name]["ffn"]
        out[name] = fill_one(tiered[name], ffn["w_gate"], ffn["w_up"], ffn["w_down"])
    if slots:
        stack = {}
        for s in slots:
            per_group = []
            for g in range(n_groups):
                st_g = jax.tree.map(lambda a: a[g], tiered["stack"][s])
                ffn = jax.tree.map(lambda a: a[g], params["stack"][s]["ffn"])
                per_group.append(
                    fill_one(st_g, ffn["w_gate"], ffn["w_up"], ffn["w_down"])
                )
            stack[s] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
        out["stack"] = stack
    return out


def strip_expert_weights(params: Params, cfg: ModelConfig) -> Params:
    """Drop flat expert weights from serving params (they live in the tier
    buffers); router + shared experts stay."""
    layers, slots, n_groups = moe_slot_names(cfg)

    def strip(ffn):
        return {k: v for k, v in ffn.items() if k not in ("w_gate", "w_up", "w_down")}

    out = jax.tree.map(lambda x: x, params)  # shallow copy of structure
    out = dict(params)
    for name in layers:
        out[name] = {**params[name], "ffn": strip(params[name]["ffn"])}
    if slots:
        stack = dict(params["stack"])
        for s in slots:
            stack[s] = {**stack[s], "ffn": strip(stack[s]["ffn"])}
        out["stack"] = stack
    return out


class EngineStats(RegistryStats):
    """Registry-backed engine counters (repro.obs) under the `engine.*`
    prefix; field access (`stats.steps += 1`,
    `stats.plan_latency_s.append(...)`) is source-compatible with the
    old dataclass. The ServingLoop passes its shared registry so these
    land on the same snapshot as the `serving.*` / `predictor.*`
    metrics; a bare `EngineStats()` is standalone."""

    PREFIX = "engine"
    COUNTERS = {
        "steps": ("steps", "decode steps dispatched"),
        "prefills": ("rows", "prefill rows computed"),
        "prefill_tokens": ("tokens", "real prompt tokens prefilled"),
        "migrations": ("moves", "expert moves emitted by planning"),
        "plans": ("plans", "layers that emitted at least one move"),
        "replans": ("passes", "plan_migrations passes over all layers"),
        "thrash_events": (
            "events", "tier flip-flops within policy.thrash_window"),
    }
    HISTS = {
        "plan_latency_s": ("s", "host-side plan_migrations latency"),
    }


class TriMoEServingEngine:
    """Host-side online loop at smoke/example scale (single device).

    `cache` may be a raw cache pytree (legacy full-batch stepping) or a
    SlotKVCache (continuous batching: the ServingLoop admits requests
    into slots, and decode gathers/scatters only the active zigzag
    group's rows). `cold_capacity_frac=1.0` keeps the tiered runtime
    exactly dropless so batched serving is token-for-token identical to
    single-request generation; lower it to trade exactness for dispatch
    buffer size (paper §Perf).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Params,
        cache,
        tiered: Params,
        sizes: Optional[TierSizes] = None,
        plan_size: Optional[int] = None,  # DEPRECATED -> policy.plan_size
        thresholds: Optional[TierThresholds] = None,  # DEPRECATED -> policy
        cold_capacity_frac: float = 1.0,
        prefill_rows: int = 4,  # bucketed prefill batch width (row pad)
        scheduler: Optional[SchedulerPolicy] = None,
        obs=None,  # Observability | ObsConfig | None (repro.obs)
    ):
        assert cfg.moe is not None, "TriMoE engine requires a routed-MoE arch"
        self.cfg = cfg
        # observability resolves like the scheduler/kernel knobs:
        # explicit obs= > cfg.obs > defaults. The ServingLoop passes its
        # own Observability so loop, engine, and predictor share one
        # registry (one snapshot) and one trace timeline.
        self.obs = resolve_obs(cfg, obs, caller="TriMoEServingEngine")
        self._tr = self.obs.tracer
        self.params = strip_expert_weights(params, cfg)
        self.kv = (
            cache if isinstance(cache, (SlotKVCache, PagedKVCache))
            else SlotKVCache.from_cache(cache)
        )
        self.tiered = tiered
        self.sizes = sizes or tier_sizes(cfg)
        self.policy = resolve_policy(
            cfg, scheduler, plan_size=plan_size, thresholds=thresholds,
            caller="TriMoEServingEngine",
        )
        self.th = self.policy.thresholds
        self.cold_capacity_frac = cold_capacity_frac
        n_moe = sum(cfg.uses_moe_layer(i) for i in range(cfg.n_layers))
        self.predictor = EMALoadPredictor(
            n_moe, cfg.moe.n_experts, alpha=self.policy.ema_alpha,
            thresholds=self.th, hysteresis=self.policy.hysteresis,
            registry=self.obs.registry,
        )
        self.domains = TPUDomains()
        self.shape = ExpertShape(cfg.d_model, cfg.moe.d_expert)
        self.stats = EngineStats(self.obs.registry)
        # thrash bookkeeping: (layer, expert) -> (replan idx, src tier)
        # of its latest migration; returning to the tier it left within
        # policy.thrash_window replans counts as a thrash event.
        self._move_history: Dict[tuple, tuple] = {}
        self._unapplied: Optional[list] = None
        # resolved kernel backends this engine's jitted closures capture
        # (kernels/backend.py; cfg.moe_backend / cfg.paged_attn_backend) —
        # observability for serving_bench's backend comparisons
        from repro.kernels.paged_attention import resolve_backend
        from repro.models.moe import moe_backend

        self.moe_backend = moe_backend(cfg)
        self.paged_attn_backend = resolve_backend(
            getattr(cfg, "paged_attn_backend", "auto")
        )
        self._step = jax.jit(
            lambda p, t, c, pos, ts: decode_step(
                p, cfg, t, c, pos, tiered=ts,
                cold_capacity_frac=cold_capacity_frac,
            )
        )

        def step_slots(p, t, c, idx, pos, ts, live):
            sub = gather_slots(c, idx)
            logits, sub, counts = decode_step(
                p, cfg, t, sub, pos, tiered=ts,
                cold_capacity_frac=cold_capacity_frac, token_mask=live,
            )
            return logits, scatter_slots(c, sub, idx), counts

        self._step_slots = jax.jit(step_slots)
        self._prefill = jax.jit(
            lambda p, toks, ts, cache_len: prefill(
                p, cfg, {"tokens": toks}, cache_len=cache_len, tiered=ts,
                cold_capacity_frac=cold_capacity_frac,
            ),
            static_argnums=(3,),
        )

        def prefill_masked(p, toks, lens, ts, cache_len):
            mask = jnp.arange(toks.shape[1])[None, :] < lens[:, None]
            return prefill(
                p, cfg, {"tokens": toks}, cache_len=cache_len, tiered=ts,
                cold_capacity_frac=cold_capacity_frac, token_mask=mask,
            )

        self._prefill_masked = jax.jit(prefill_masked, static_argnums=(4,))

        # --- paged-KV variants: decode/prefill against the block pools
        def step_paged(p, t, pools, states, tables, idx, pos, ts, live):
            sub = gather_slots(states, idx)
            logits, new_pools, new_sub, counts = decode_step_paged(
                p, cfg, t, pools, sub, tables, pos, tiered=ts,
                cold_capacity_frac=cold_capacity_frac, token_mask=live,
            )
            return logits, new_pools, scatter_slots(states, new_sub, idx), counts

        self._step_paged = jax.jit(step_paged)

        def prefill_paged_fn(p, toks, lens, past, tables, pools, ts):
            mask = jnp.arange(toks.shape[1])[None, :] < lens[:, None]
            return prefill_paged(
                p, cfg, {"tokens": toks}, pools, tables, past, mask,
                tiered=ts, cold_capacity_frac=cold_capacity_frac,
            )

        self._prefill_paged = jax.jit(prefill_paged_fn)

        # speculative verify: chunk-of-k through the SAME chunked paged
        # kernels, but keeping every chunk position's logits + the
        # expert counts (models.decode_verify)
        def verify_paged_fn(p, toks, lens, past, tables, pools, ts):
            mask = jnp.arange(toks.shape[1])[None, :] < lens[:, None]
            return decode_verify(
                p, cfg, toks, pools, tables, past, mask,
                tiered=ts, cold_capacity_frac=cold_capacity_frac,
            )

        self._verify_paged = jax.jit(verify_paged_fn)
        self.prefill_rows = prefill_rows
        self.decode_table_widths = set()  # distinct sliced widths (pow2)
        self.prefill_table_widths = set()  # paged prefill's sliced widths
        self.verify_widths = set()  # pow2-padded chunk-of-k widths
        self.verify_table_widths = set()  # verify's sliced table widths
        self._migrate = jax.jit(apply_migrations)

        # stacked tier buffers migrate in ONE fused jit: extract group g,
        # swap, write back — eager per-leaf a[g] / .at[g].set dispatches
        # copy the whole stack per leaf and dominate replan cost at
        # smoke scale. g is traced (weak scalar), so one compile serves
        # every group.
        def migrate_stack(stack_state, plan, g):
            sub = jax.tree.map(lambda a: a[g], stack_state)
            new = apply_migrations(sub, plan)
            return jax.tree.map(lambda a, n: a.at[g].set(n), stack_state, new)

        self._migrate_stack = jax.jit(migrate_stack)
        self._layer_keys = self._flatten_layer_keys()
        # persistent host mirror of each layer's (expert_tier, expert_slot),
        # lazily seeded from device state: planning then never needs a
        # device->host sync. plan_migrations mutates it in lockstep with
        # the swaps it emits (the apply-before-next-plan assertion keeps
        # mirror and device from diverging).
        self._host_layout: Dict[int, tuple] = {}

    # cache is owned by the SlotKVCache so the loop and engine share one
    # source of truth; keep attribute-style access for legacy callers.
    @property
    def cache(self):
        assert isinstance(self.kv, SlotKVCache), (
            "raw-cache access is a SlotKVCache affordance; the paged "
            "layout exposes kv.pools / kv.slot_state"
        )
        return self.kv.cache

    @cache.setter
    def cache(self, value):
        self.kv.cache = value

    def _flatten_layer_keys(self) -> List[tuple]:
        """Ordered (kind, name, group) keys, one per MoE layer."""
        layers, slots, n_groups = moe_slot_names(self.cfg)
        keys = [("layer", n, 0) for n in layers]
        for g in range(n_groups):
            for s in slots:
                keys.append(("stack", s, g))
        return keys

    def _get_state(self, key) -> Params:
        kind, name, g = key
        if kind == "layer":
            return self.tiered[name]
        return jax.tree.map(lambda a: a[g], self.tiered["stack"][name])

    # ----------------------------------------------------------- stepping
    def step(self, tokens: jnp.ndarray, pos: int):
        """Full-batch decode step + synchronous replan (legacy path)."""
        logits, self.cache, counts = self._step(
            self.params, tokens, self.cache, jnp.asarray(pos, jnp.int32), self.tiered
        )
        counts = np.asarray(counts)
        self.stats.steps += 1
        self.replan(counts)
        return logits

    def step_slots(self, tokens, pos, slot_indices, live=None):
        """Decode only the cache rows in `slot_indices` (the active
        zigzag group): gather rows -> decode -> scatter back, all inside
        one jit so the compile is reused across groups.

        tokens: [W,1] int32; pos: [W] per-slot absolute positions;
        live: optional [W] bool — dead (padded) rows are excluded from
        MoE dispatch and expert counts so the predictor only sees real
        loads. Returns (logits [W,V], expert_counts) WITHOUT replanning
        — the serving loop replans from the previous group's counts
        while this group's step is in flight (zigzag overlap), via
        `replan`.
        """
        idx = jnp.asarray(slot_indices, jnp.int32)
        if live is None:
            live = jnp.ones((idx.shape[0],), bool)
        logits, self.kv.cache, counts = self._step_slots(
            self.params, jnp.asarray(tokens), self.kv.cache, idx,
            jnp.asarray(pos, jnp.int32), self.tiered, jnp.asarray(live, bool),
        )
        self.stats.steps += 1
        return logits, counts

    def prefill_slots(self, prompts, slot_indices, lengths=None):
        """Prefill newly admitted requests into their cache slots.

        prompts: [W, S] int32; runs the full-sequence forward through
        the tiered MoE runtime (engine params are stripped) and scatters
        the resulting rows into the slot cache. Returns per-row logits
        [W, V] — the first generated token.

        Without `lengths`, every row is exactly S real tokens (legacy
        exact-length path: one compile per distinct S). With `lengths`
        [W], rows are RIGHT-padded to a shared bucket width S and run
        through the MASKED prefill: pad keys masked out of attention,
        recurrent states carry through pads, each row's cache written at
        its true length, logits gathered at the last real token. Rows
        are additionally padded up to `prefill_rows` (excess chunked),
        so the jit only ever compiles (prefill_rows, bucket_width)
        shapes — at most one compile per bucket-table entry
        (`prefill_compiles`).
        """
        assert self.kv.seq_len is not None, (
            "prefill_slots needs a SlotKVCache built with an explicit seq_len"
        )
        if lengths is None:
            prompts = jnp.asarray(prompts, jnp.int32)
            logits, sub_cache = self._prefill(
                self.params, prompts, self.tiered, self.kv.seq_len
            )
            self.kv.scatter(sub_cache, slot_indices)
            self.stats.prefills += prompts.shape[0]
            self.stats.prefill_tokens += int(prompts.shape[0] * prompts.shape[1])
            return logits

        prompts = np.asarray(prompts, np.int32)
        lengths = np.asarray(lengths, np.int32)
        n, width = prompts.shape
        assert len(slot_indices) == n and lengths.shape == (n,)
        assert np.all(lengths <= width) and np.all(lengths > 0)
        r = self.prefill_rows
        out = []
        for c0 in range(0, n, r):
            nr = min(r, n - c0)
            toks = np.zeros((r, width), np.int32)
            lens = np.zeros((r,), np.int32)  # dummy rows: all-pad mask
            toks[:nr] = prompts[c0:c0 + nr]
            lens[:nr] = lengths[c0:c0 + nr]
            logits, sub_cache = self._prefill_masked(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                self.tiered, self.kv.seq_len,
            )
            if nr < r:  # drop the dummy rows before scattering
                sub_cache = gather_slots(sub_cache, list(range(nr)))
            self.kv.scatter(sub_cache, list(slot_indices[c0:c0 + nr]))
            out.append(logits[:nr])
            self.stats.prefills += nr
            self.stats.prefill_tokens += int(lens.sum())
        return out[0] if len(out) == 1 else jnp.concatenate(out)

    def _active_table_width(self, pos, live) -> int:
        """Block-table columns decode actually needs this step (the
        decode analogue of the prefill bucket bound — pow2 widths, at
        most log2(blocks_per_slot) compiles per group width)."""
        from repro.kernels.paged_attention import active_block_width

        mx = int(pos[live].max()) if live.any() else 0
        return active_block_width(
            mx, self.kv.block_size, max(1, self.kv.blocks_per_slot)
        )

    def step_slots_paged(self, tokens, pos, slot_indices, tables, live=None):
        """Paged decode of the active zigzag group: recurrent state rows
        gather/scatter by slot index as in `step_slots`, while attention
        K/V reads and writes go through the shared block pools by each
        row's block table (`tables` [W, nb] int32). The table is SLICED
        to the pow2-bucketed active width first, so decode attention
        (Pallas kernel or dense-gather ref) touches O(longest live row)
        blocks instead of the full `blocks_per_slot` — positions beyond
        a row's length were masked to exp(-inf) = 0 exactly, so the
        slice is numerics-preserving. Returns (logits, expert_counts)
        without replanning — see `step_slots`."""
        assert isinstance(self.kv, PagedKVCache)
        idx = jnp.asarray(slot_indices, jnp.int32)
        live = (
            np.ones((len(slot_indices),), bool) if live is None
            else np.asarray(live, bool)
        )
        pos = np.asarray(pos, np.int64)
        # dead rows still write their (garbage) K/V — point them at the
        # trash block so a just-completed slot can never corrupt its own
        # (possibly shared / radix-indexed) blocks before recycling
        tables = np.array(tables, np.int32, copy=True)
        tables[~live] = self.kv.trash
        if self.kv.sanitizer is not None:
            # the blocks this step's token writes actually land in: each
            # row's table entry at its decode position (dead rows were
            # just trash-routed above — validated on the real values)
            lb = np.clip(pos // self.kv.block_size, 0, tables.shape[1] - 1)
            self.kv.sanitizer.check_scatter_targets(
                tables[np.arange(len(pos)), lb], live
            )
        width = self._active_table_width(pos, live)
        self.decode_table_widths.add(width)
        tables = tables[:, :width]
        logits, self.kv.pools, self.kv.slot_state, counts = self._step_paged(
            self.params, jnp.asarray(tokens), self.kv.pools,
            self.kv.slot_state, jnp.asarray(tables), idx,
            jnp.asarray(pos, jnp.int32), self.tiered, jnp.asarray(live, bool),
        )
        self.stats.steps += 1
        return logits, counts

    def prefill_slots_paged(self, suffixes, slot_indices, lengths, past_len):
        """Chunked suffix-only masked prefill into paged slots.

        suffixes: [W, S] int32 — each row's UNCACHED prompt suffix (or
        one piggyback chunk of it), right-padded to a shared bucket
        width; lengths [W] real suffix lengths; past_len [W] tokens
        already cached before the chunk (0 = cold admission; prefix hit
        or earlier chunks otherwise). The rows' block tables must
        already cover prefix + suffix (PagedKVCache.admit_slot).

        Block tables are SLICED to the pow2-bucketed active width
        covering the furthest row end (prefix + suffix — the prefill
        analogue of `step_slots_paged`'s decode slicing), so past-K/V
        attention reads O(active blocks), not O(blocks_per_slot). Rows
        are padded to `prefill_rows` (excess chunked) so the jit
        compiles one (prefill_rows, bucket width, table width) shape —
        at most len(bucket_table) x n_width_buckets(blocks_per_slot)
        compiles (`prefill_compiles`, gated in CI).
        Returns per-row last-real-token logits [W, V].
        """
        from repro.kernels.paged_attention import active_block_width

        assert isinstance(self.kv, PagedKVCache)
        suffixes = np.asarray(suffixes, np.int32)
        lengths = np.asarray(lengths, np.int32)
        past_len = np.asarray(past_len, np.int32)
        n, width = suffixes.shape
        assert len(slot_indices) == n
        assert np.all(lengths > 0) and np.all(lengths <= width)
        r = self.prefill_rows
        out = []
        for c0 in range(0, n, r):
            nr = min(r, n - c0)
            end = int((past_len[c0:c0 + nr] + lengths[c0:c0 + nr]).max())
            tw = active_block_width(
                end - 1, self.kv.block_size, max(1, self.kv.blocks_per_slot)
            )
            self.prefill_table_widths.add(tw)
            toks = np.zeros((r, width), np.int32)
            lens = np.zeros((r,), np.int32)  # dummy rows: all-pad mask
            past = np.zeros((r,), np.int32)
            tables = np.full((r, tw), self.kv.trash, np.int32)
            toks[:nr] = suffixes[c0:c0 + nr]
            lens[:nr] = lengths[c0:c0 + nr]
            past[:nr] = past_len[c0:c0 + nr]
            tables[:nr] = self.kv.table_rows(slot_indices[c0:c0 + nr])[:, :tw]
            if self.kv.sanitizer is not None:
                # every block this chunk writes — the suffix span
                # [past, past+len) of each real row — must be private;
                # dummy pad rows must be all-trash
                bs = self.kv.block_size
                bids, mask = [], []
                for j in range(r):
                    lo, hi = int(past[j]) // bs, -(-int(past[j] + lens[j]) // bs)
                    span = tables[j, lo:hi] if j < nr else tables[j]
                    bids.extend(span.tolist())
                    mask.extend([j < nr] * len(span))
                self.kv.sanitizer.check_scatter_targets(bids, mask)
            logits, self.kv.pools, row_states = self._prefill_paged(
                self.params, jnp.asarray(toks), jnp.asarray(lens),
                jnp.asarray(past), jnp.asarray(tables), self.kv.pools,
                self.tiered,
            )
            if nr < r:  # drop the dummy rows before scattering state
                row_states = gather_slots(row_states, list(range(nr)))
            self.kv.slot_state = scatter_slots(
                self.kv.slot_state, row_states, list(slot_indices[c0:c0 + nr])
            )
            out.append(logits[:nr])
            self.stats.prefills += nr
            self.stats.prefill_tokens += int(lens.sum())
        return out[0] if len(out) == 1 else jnp.concatenate(out)

    def verify_slots_paged(self, chunks, slot_indices, lengths, past_len,
                           live=None):
        """Speculative chunk-of-k verification of the active zigzag
        group against the paged pools.

        chunks: [W, K] int32 — each row's [sampled token, draft_1..]
        chunk, right-padded; lengths [W] real chunk tokens per row (a
        row with no drafts verifies a chunk of 1 — exactly its plain
        decode step); past_len [W] the rows' committed lengths before
        the chunk. The caller must have `ensure_block`'d every chunk
        position (ServingLoop._spec_step) — rejected positions are
        rolled back afterwards via PagedKVCache.truncate.

        Same compile accounting as decode/prefill: the chunk width pads
        to pow2 (at most log2(k)+1 widths) and block tables slice to
        the pow2 active width, so compiles are bounded by
        n_chunk_widths x n_width_buckets (`verify_compiles`).

        Returns (logits [W, Kp, V], expert_counts) — position i's
        logits condition on chunk tokens 0..i and the cached prefix,
        bit-exact vs sequential decode in fp32."""
        from repro.kernels.paged_attention import active_block_width

        assert isinstance(self.kv, PagedKVCache)
        chunks = np.asarray(chunks, np.int32)
        lengths = np.asarray(lengths, np.int32)
        past_len = np.asarray(past_len, np.int32)
        n, width = chunks.shape
        assert len(slot_indices) == n
        live = (
            np.ones((n,), bool) if live is None else np.asarray(live, bool)
        )
        assert np.all(lengths[live] > 0) and np.all(lengths <= width)
        kw = 1
        while kw < width:
            kw *= 2
        toks = np.zeros((n, kw), np.int32)
        toks[:, :width] = chunks
        lens = np.where(live, lengths, 0).astype(np.int32)
        past = np.where(live, past_len, 0).astype(np.int32)
        end = int((past + lens).max()) if live.any() else 1
        tw = active_block_width(
            end - 1, self.kv.block_size, max(1, self.kv.blocks_per_slot)
        )
        self.verify_widths.add(kw)
        self.verify_table_widths.add(tw)
        # dead rows: all-trash tables + zero mask, like prefill pads
        tables = np.full((n, tw), self.kv.trash, np.int32)
        rows = self.kv.table_rows(slot_indices)[:, :tw]
        tables[live] = rows[live]
        if self.kv.sanitizer is not None:
            # the chunk writes span [past, past+len) of each live row —
            # every target block must be private; dead rows all-trash
            bs = self.kv.block_size
            bids, mask = [], []
            for j in range(n):
                if live[j]:
                    lo = int(past[j]) // bs
                    hi = -(-int(past[j] + lens[j]) // bs)
                    span = tables[j, lo:hi]
                else:
                    span = tables[j]
                bids.extend(span.tolist())
                mask.extend([bool(live[j])] * len(span))
            self.kv.sanitizer.check_scatter_targets(bids, mask)
        logits, self.kv.pools, row_states, counts = self._verify_paged(
            self.params, jnp.asarray(toks), jnp.asarray(lens),
            jnp.asarray(past), jnp.asarray(tables), self.kv.pools,
            self.tiered,
        )
        live_rows = [j for j in range(n) if live[j]]
        if live_rows:  # dead rows must not clobber their slot state
            sub = gather_slots(row_states, live_rows)
            self.kv.slot_state = scatter_slots(
                self.kv.slot_state, sub,
                [int(slot_indices[j]) for j in live_rows],
            )
        self.stats.steps += 1
        return logits, counts

    @property
    def verify_compiles(self) -> int:
        """Distinct jit compiles of the speculative verify — bounded by
        pow2 chunk widths x table-width buckets (the CI spec gate reads
        this through serving_bench --spec)."""
        return int(self._verify_paged._cache_size())

    @property
    def prefill_compiles(self) -> int:
        """Distinct jit compiles of the bucketed masked prefill across
        BOTH variants — the contiguous slot path (bounded by
        len(bucket_table)) and the paged/chunked path (bounded by
        len(bucket_table) x n_width_buckets(blocks_per_slot), the
        table-width slicing factor) — the quantity the CI compile-count
        gate bounds (benchmarks/serving_bench.py)."""
        return int(
            self._prefill_masked._cache_size()
            + self._prefill_paged._cache_size()
        )

    # ---------------------------------------------------------- migration
    def _tier_cost(self, tier: int, load: float) -> float:
        """Per-step execution time of one expert in a tier under the TPU
        domain cost model (core.cost_model.TPUDomains)."""
        load = max(float(load), 1.0)
        if tier == HOT:
            return self.domains.t_replicated(self.shape, load)
        if tier == WARM:
            return self.domains.t_striped(self.shape, load)
        return self.domains.t_localized(self.shape, load)

    def _tier_costs(self, loads: np.ndarray) -> np.ndarray:
        """Vectorized `_tier_cost`: [3, *loads.shape] seconds for every
        expert in every tier (loads clamped to >= 1 token, like the
        scalar). Accepts one layer's [E] loads or the whole [L, E] EMA."""
        loads = np.maximum(np.asarray(loads, np.float64), 1.0)
        costs = np.empty((3,) + loads.shape)
        costs[HOT] = self.domains.v_replicated(self.shape, loads)
        costs[WARM] = self.domains.v_striped(self.shape, loads)
        costs[COLD] = self.domains.v_localized(self.shape, loads)
        return costs

    @property
    def swap_cost_s(self) -> float:
        """Cost of one expert migration: both experts' weight stacks
        cross the resharding collective (the DIMM-Link relayout
        analogue) — the breakeven bar dynamic plan sizing charges each
        candidate move against."""
        hw = self.domains.hw
        return 2.0 * self.shape.weight_bytes / (hw.ici_link_bw * hw.ici_links)

    def observe(self, counts: np.ndarray) -> None:
        """Feed realized per-layer expert loads to the EMA predictor
        (Eq. 8). Runs every step, even under `policy.freeze` — the
        static baseline still reports predictor accuracy."""
        counts = np.asarray(counts)
        for li in range(len(self._layer_keys)):
            self.predictor.update(li, counts[li])

    def plan_migrations(self) -> list:
        """Draw migration plans from the predictor's hysteresis tier
        decisions WITHOUT applying them.

        Returns [(layer_key, plan_array)] — hand the list to
        `apply_planned` (the serving loop defers that until the next
        decode step is in flight, overlapping the swap collectives with
        compute). Plan arrays always have `policy.plan_rows` rows
        (no-op rows = -1), so the jitted `apply_migrations` compiles
        once.

        Sizing is cost-model-driven when `policy.plan_size` is None: a
        move is admitted while its per-step benefit (TPU-domain cost
        delta at the predicted load) amortized over
        `policy.amortize_steps` exceeds `swap_cost_s`, clamped to
        [plan_min, plan_max]. Moves draining the current bottleneck
        tier are ranked first (§4.2 refinement). Flip-flops within
        `policy.thrash_window` replans are counted as thrash events."""
        assert self._unapplied is None, (
            "plan_migrations called with unapplied plans pending; call "
            "apply_planned first"
        )
        t0 = time.perf_counter()
        policy = self.policy
        self.stats.replans += 1
        r_idx = self.stats.replans
        if self._tr.enabled:
            # tier timeline channel: one counter sample per replan of
            # where experts sit (decided tiers) and where predicted load
            # mass sits — the stacked Perfetto tracks relayout decisions
            # are audited against
            occ = tier_occupancy(self.predictor.decided, self.predictor.ema)
            self._tr.counter(
                "tier/experts",
                {k: v for k, v in occ.items() if k.endswith("_experts")},
                cat="tier",
            )
            self._tr.counter(
                "tier/predicted_load",
                {k: v for k, v in occ.items() if k.endswith("_load")},
                cat="tier",
            )
        plans: list = []
        if policy.freeze:
            self.stats.plan_latency_s.append(time.perf_counter() - t0)
            return plans
        swap_cost = self.swap_cost_s
        # one vectorized cost evaluation for ALL layers (the planner
        # runs on the decode hot path; per-layer numpy round trips were
        # a measurable fraction of a smoke-scale step)
        costs_all = (
            self._tier_costs(self.predictor.ema)
            if policy.cost_mode == "tpu" else None
        )
        e_idx = np.arange(self.predictor.ema.shape[1])
        for li, key in enumerate(self._layer_keys):
            decided = self.predictor.decided[li]
            if li not in self._host_layout:
                state = self._get_state(key)
                self._host_layout[li] = (
                    np.array(state["expert_tier"], copy=True),
                    np.array(state["expert_slot"], copy=True),
                )
            cur_tier, cur_slot = self._host_layout[li]
            moves = np.nonzero(decided != cur_tier)[0]
            if len(moves) == 0:
                continue
            ema = self.predictor.ema[li]
            if policy.cost_mode == "tpu":
                cur_cost = costs_all[cur_tier, li, e_idx]
                delta = cur_cost - costs_all[decided, li, e_idx]
                tier_time = np.bincount(
                    cur_tier, weights=cur_cost, minlength=3
                )
            else:  # "loads": pure EMA-mass ranking, no breakeven gate
                delta = ema.astype(np.float64)
                tier_time = np.bincount(cur_tier, weights=ema, minlength=3)
            if (
                policy.plan_size is None
                and policy.plan_min == 0
                and policy.cost_mode == "tpu"
                and not (delta[moves] * policy.amortize_steps > swap_cost).any()
            ):
                continue  # nothing clears breakeven; skip the ordering work
            benefit = {int(e): float(delta[e]) for e in moves}
            # bottleneck-aware ordering: moves that drain the most
            # expensive tier first, then by predicted benefit
            bottleneck = int(np.argmax(tier_time))
            order = sorted(
                (int(e) for e in moves),
                key=lambda e: (0 if cur_tier[e] == bottleneck else 1, -benefit[e]),
            )
            if policy.plan_size is not None:
                chosen = order[: policy.plan_size]
            else:
                chosen = [
                    e for e in order
                    if policy.cost_mode != "tpu"
                    or benefit[e] * policy.amortize_steps > swap_cost
                ][: policy.plan_max]
                if len(chosen) < policy.plan_min:
                    backfill = [e for e in order if e not in chosen]
                    chosen += backfill[: policy.plan_min - len(chosen)]
            if not chosen:
                continue
            plan = np.full((policy.plan_rows, 5), -1, np.int32)
            emitted = 0
            for e in chosen:
                dst_tier = int(decided[e])
                # victim: lowest-EMA expert currently in the target tier
                in_dst = np.nonzero(cur_tier == dst_tier)[0]
                if len(in_dst) == 0:
                    continue
                victim = in_dst[np.argmin(ema[in_dst])]
                e_tier, e_slot = int(cur_tier[e]), int(cur_slot[e])
                v_slot = int(cur_slot[victim])
                plan[emitted] = (e, e_tier, e_slot, dst_tier, v_slot)
                emitted += 1
                # maintain the host mirror (swap)
                cur_tier[victim], cur_slot[victim] = e_tier, e_slot
                cur_tier[e], cur_slot[e] = dst_tier, v_slot
                self.stats.migrations += 1
                prev = self._move_history.get((li, e))
                if (
                    prev is not None
                    and prev[1] == dst_tier
                    and r_idx - prev[0] <= policy.thrash_window
                ):
                    self.stats.thrash_events += 1
                    if self._tr.enabled:
                        self._tr.instant(
                            "thrash", cat="tier", layer=li, expert=int(e),
                            back_to=dst_tier,
                        )
                self._move_history[(li, e)] = (r_idx, e_tier)
            if emitted == 0:
                continue
            plans.append((key, plan))
            self.stats.plans += 1
        if plans:
            self._unapplied = plans
        self.stats.plan_latency_s.append(time.perf_counter() - t0)
        return plans

    def apply_planned(self, plans: list) -> None:
        """Dispatch the jitted weight swaps for plans from
        `plan_migrations`. Fixed-shape plan arrays mean exactly one
        compile of `apply_migrations` per tier-buffer structure."""
        if not plans:
            self._unapplied = None
            return
        tr = self._tr
        with tr.span("migrate", cat="scheduler"):
            for key, plan in plans:
                kind, name, g = key
                if tr.enabled:
                    # one instant per migrated layer on the tier channel
                    tr.instant(
                        "tier_migration", cat="tier",
                        layer=f"{kind}:{name}:g{g}",
                        moves=int((plan[:, 0] >= 0).sum()),
                    )
                if kind == "layer":
                    self.tiered[name] = self._migrate(
                        self.tiered[name], jnp.asarray(plan)
                    )
                else:
                    self.tiered["stack"][name] = self._migrate_stack(
                        self.tiered["stack"][name], jnp.asarray(plan), g
                    )
        self._unapplied = None

    def replan(self, counts: np.ndarray) -> None:
        """Legacy synchronous path: observe + plan + apply in one call
        (`engine.step` and pre-PR-7 callers)."""
        self.observe(counts)
        self.apply_planned(self.plan_migrations())

    _replan = replan  # legacy name
