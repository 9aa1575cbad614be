"""TriMoE serving engine: the online loop of paper §4 on the TPU runtime.

Per decode step:
  1. jitted `forward_paged(..., tiered=...)` executes attention + the
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
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.cost_model import ExpertShape, TPUDomains
from repro.core.policy import SchedulerPolicy, resolve_policy
from repro.core.predictor import EMALoadPredictor
from repro.core.tiers import COLD, HOT, WARM, TierThresholds
from repro.models.layers import Params
from repro.models.model import forward_paged, layer_signature, stack_plan
from repro.obs import resolve_obs
from repro.obs.metrics import RegistryStats
from repro.serving.kv_cache import gather_slots, scatter_slots
from repro.serving.paged_kv import PagedKVCache
from repro.serving.tiered_moe import (
    AWAY_T,
    TIER_KEYS,
    TierSizes,
    apply_migrations,
    init_tiered_state,
    tier_capacity,
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
            for e in np.nonzero(tier == tid)[0]:  # global ids of held experts
                buf[slot[e]] = np.asarray(wstack[e - cfg.moe.expert_offset])
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
        # --- MoE dispatch, from the [L, E] expert counts `observe` gets
        "moe_steps": ("steps", "steps whose expert counts were observed"),
        "moe_assignments": (
            "pairs", "live (token, expert) pairs routed to held experts"),
        "experts_touched": ("cells", "(layer, held expert) cells routed to"),
        "expert_rows": (
            "rows", "rows the tier FFNs computed: slots x capacity"),
        "routed_hot": ("pairs", "routed pairs the hot tier served"),
        "routed_warm": ("pairs", "routed pairs the warm tier served"),
        "routed_cold": ("pairs", "routed pairs the cold tier served"),
        "routed_elsewhere": (
            "pairs", "routed pairs whose expert is held on another chip"),
        # --- paged attention, per layer, from each call's positions and
        # sliced table width (kernels/paged_attention)
        "attn_kv_blocks": (
            "blocks", "pool blocks the rows read, through each last query"),
        "attn_kv_steps": (
            "steps", "steps the kernel walks them in (P blocks a step)"),
    }
    HISTS = {
        "plan_latency_s": ("s", "host-side plan_migrations latency"),
    }


class MoEDispatch(NamedTuple):
    """What one MoE step was dispatched with, to pair with the expert
    counts it returns: the tokens each MoE layer dispatched (every tier
    buffer's capacity follows from it) and the [L, E] `expert_tier`
    table the step read (the applied layout, not the planner's)."""

    tokens: int
    tier: np.ndarray


class TriMoEServingEngine:
    """Host-side online loop at smoke/example scale (single device).

    `kv` is the PagedKVCache the ServingLoop admits requests into. Decode
    steps, prefill chunks and speculative verify chunks are one paged
    forward pass (`models.model.forward_paged`), prepared on the host by
    one path (`_run_paged`): decode steps a zigzag group's rows by one
    token, prefill writes admitted rows' uncached suffixes, verify
    scores a group's draft chunks. The MoE layers run through the
    tiered runtime dropless, so batched serving is token-for-token
    identical to single-request generation.

    The engine owns `tiered`: migrations donate the tier arrays and
    swap experts in place, so once `apply_planned` has run, the arrays
    the caller handed in are deleted and `self.tiered` holds their
    successors. A caller that reads its own tier arrays afterwards
    passes the engine a copy. Likewise the paged calls donate the KV
    pools: after a decode, prefill or verify, `kv.pools` holds the only
    live pool arrays.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Params,
        kv: PagedKVCache,
        tiered: Params,
        sizes: Optional[TierSizes] = None,
        plan_size: Optional[int] = None,  # DEPRECATED -> policy.plan_size
        thresholds: Optional[TierThresholds] = None,  # DEPRECATED -> policy
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
        self.kv = kv
        self.tiered = tiered
        self.sizes = sizes or tier_sizes(cfg)
        self.policy = resolve_policy(
            cfg, scheduler, plan_size=plan_size, thresholds=thresholds,
            caller="TriMoEServingEngine",
        )
        self.th = self.policy.thresholds
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

        # --- the paged forward, jitted per phase (the XLA modules keep
        # the names `jit_step_paged` and `jit_prefill_paged_fn`, which
        # the benchmark's trace reads). The pools are donated: each call
        # writes its tokens' K/V into the caller's pool buffers in place,
        # so one pool is live however many steps are in flight (kv.pools
        # holds the successor). Decode steps the rows' recurrent state;
        # prefill and verify chunks start it from zero.
        def step_paged(p, t, pools, states, tables, idx, pos, ts, live):
            return forward_paged(
                p, cfg, t, pools, gather_slots(states, idx), tables, pos,
                live[:, None], ts,
            )

        def chunk_mask(toks, lens):
            return jnp.arange(toks.shape[1])[None, :] < lens[:, None]

        def prefill_paged_fn(p, toks, lens, past, tables, pools, ts):
            logits, pools, rows, _ = forward_paged(
                p, cfg, toks, pools, None, tables, past,
                chunk_mask(toks, lens), ts,
            )
            return logits, pools, rows, None  # prefill observes no loads

        def verify_paged_fn(p, toks, lens, past, tables, pools, ts):
            return forward_paged(
                p, cfg, toks, pools, None, tables, past,
                chunk_mask(toks, lens), ts, all_logits=True,
            )

        self._step_paged = jax.jit(step_paged, donate_argnums=2)
        self._prefill_paged = jax.jit(prefill_paged_fn, donate_argnums=5)
        self._verify_paged = jax.jit(verify_paged_fn, donate_argnums=5)
        self.prefill_rows = prefill_rows
        self.decode_table_widths = set()  # distinct sliced widths (pow2)
        self.prefill_table_widths = set()  # paged prefill's sliced widths
        self.verify_widths = set()  # pow2-padded chunk-of-k widths
        self.verify_table_widths = set()  # verify's sliced table widths
        # the tier state is donated: each swap rewrites only the slot
        # windows it touches, in place (tiered_moe.apply_migrations).
        # Stacked tier buffers migrate group g of the stack; g is traced,
        # so one compile serves every group. The XLA module keeps its
        # name, `jit_migrate_stack`, which the benchmark's trace reads.
        self._migrate = jax.jit(apply_migrations, donate_argnums=0)

        def migrate_stack(stack_state, plan, g):
            return apply_migrations(stack_state, plan, g)

        self._migrate_stack = jax.jit(migrate_stack, donate_argnums=0)
        self._layer_keys = self._flatten_layer_keys()
        self._layer_index = {key: li for li, key in enumerate(self._layer_keys)}
        self._moe_rows = self._moe_count_rows()
        # the routing tables as the device holds them, read once here:
        # [L, E] expert_tier / expert_slot, replaced (never mutated) by
        # `apply_planned`, which replays each swap as apply_migrations
        # does — the layout a step is dispatched with, with no sync
        tiers, slots = zip(*map(self._read_tables, self._layer_keys))
        self._applied = (np.stack(tiers), np.stack(slots))
        # tier buffer slots summed over MoE layers, per tier (a buffer
        # is [(group,) slots, 3, D, F])
        self._tier_slots = [
            sum(self._state_ref(key)[k].shape[-4] for key in self._layer_keys)
            for k in TIER_KEYS
        ]
        self.last_dispatch: Optional[MoEDispatch] = None
        # persistent host mirror of each layer's (expert_tier,
        # expert_slot) for planning, which thus never needs a
        # device->host sync. plan_migrations mutates it in lockstep with
        # the swaps it emits, ahead of the device (the
        # apply-before-next-plan assertion keeps the two from diverging).
        # It covers the held experts only (local ids; global = local +
        # expert_offset), as do the predictor and the planner.
        self._held = slice(cfg.moe.expert_offset,
                           cfg.moe.expert_offset + cfg.moe.n_experts)
        self._host_layout: Dict[int, tuple] = {
            li: (tiers[li][self._held].copy(), slots[li][self._held].copy())
            for li in range(len(self._layer_keys))
        }

    def _flatten_layer_keys(self) -> List[tuple]:
        """Ordered (kind, name, group) keys, one per MoE layer."""
        layers, slots, n_groups = moe_slot_names(self.cfg)
        keys = [("layer", n, 0) for n in layers]
        for g in range(n_groups):
            for s in slots:
                keys.append(("stack", s, g))
        return keys

    def _moe_count_rows(self) -> np.ndarray:
        """Rows of a step's expert counts that belong to MoE layers, in
        `_layer_keys` order: the model returns one row per layer
        (unrolled layers, then each scan group's period), zeros where a
        layer has no MoE."""
        unrolled, n_groups, period = stack_plan(self.cfg)
        rows = [i for i, li in enumerate(unrolled)
                if layer_signature(self.cfg, li)[1] == "moe"]
        rows += [len(unrolled) + g * len(period) + j
                 for g in range(n_groups)
                 for j, sig in enumerate(period) if sig[1] == "moe"]
        return np.asarray(rows, np.int64)

    def _state_ref(self, key) -> Params:
        """The tier state holding layer `key`, its group axis kept (no
        device op)."""
        kind, name, _ = key
        return self.tiered[name] if kind == "layer" else self.tiered["stack"][name]

    def _read_tables(self, key) -> tuple:
        """Layer `key`'s (expert_tier, expert_slot) read to the host,
        without slicing the weight buffers on the device."""
        st = self._state_ref(key)
        out = tuple(np.array(st[f]) for f in ("expert_tier", "expert_slot"))
        return out if key[0] == "layer" else tuple(a[key[2]] for a in out)

    # ----------------------------------------------------------- stepping
    def step_slots_paged(self, tokens, pos, slot_indices, tables, live=None):
        """Decode one token for each row of the active zigzag group.

        tokens [W, 1] int32; pos [W] the rows' absolute positions;
        tables [W, nb] int32 their block tables; live [W] bool (None =
        all) — dead rows are excluded from MoE dispatch and expert
        counts, so the predictor only sees real loads. Returns (logits
        [W, V], expert_counts) WITHOUT replanning: the serving loop
        replans from the previous group's counts while this step is in
        flight (zigzag overlap), via `observe` / `plan_migrations`."""
        n = len(slot_indices)
        return self._run_paged(
            "decode", np.asarray(tokens, np.int32), np.ones(n, np.int32),
            np.asarray(pos, np.int32), slot_indices, live, tables,
        )

    def prefill_slots_paged(self, suffixes, slot_indices, lengths, past_len):
        """Chunked suffix-only masked prefill into paged slots.

        suffixes: [W, S] int32 — each row's UNCACHED prompt suffix (or
        one piggyback chunk of it), right-padded to a shared bucket
        width; lengths [W] real suffix lengths; past_len [W] tokens
        already cached before the chunk (0 = cold admission; prefix hit
        or earlier chunks otherwise). The rows' block tables must
        already cover prefix + suffix (PagedKVCache.admit_slot).

        Rows are padded to `prefill_rows` (more rows split into several
        calls), so the jit compiles one (prefill_rows, bucket width,
        table width) shape — at most len(bucket_table) x
        n_width_buckets(blocks_per_slot) compiles (`prefill_compiles`).
        Returns per-row last-real-token logits [W, V].
        """
        suffixes = np.asarray(suffixes, np.int32)
        lengths = np.asarray(lengths, np.int32)
        past_len = np.asarray(past_len, np.int32)
        n, width = suffixes.shape
        assert len(slot_indices) == n
        assert np.all(lengths > 0) and np.all(lengths <= width)
        r = self.prefill_rows
        out = [
            self._run_paged(
                "prefill", suffixes[c0:c0 + r], lengths[c0:c0 + r],
                past_len[c0:c0 + r], list(slot_indices[c0:c0 + r]),
            )
            for c0 in range(0, n, r)
        ]
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

        The chunk width pads to pow2 (at most log2(k)+1 widths), so
        compiles are bounded by n_chunk_widths x n_width_buckets
        (`verify_compiles`).

        Returns (logits [W, Kp, V], expert_counts) — position i's
        logits condition on chunk tokens 0..i and the cached prefix,
        bit-exact vs sequential decode in fp32."""
        chunks = np.asarray(chunks, np.int32)
        n, width = chunks.shape
        assert len(slot_indices) == n
        live = np.ones(n, bool) if live is None else np.asarray(live, bool)
        lengths = np.where(live, np.asarray(lengths, np.int32), 0)
        assert np.all(lengths[live] > 0) and np.all(lengths <= width)
        kw = 1
        while kw < width:
            kw *= 2
        self.verify_widths.add(kw)
        toks = np.zeros((n, kw), np.int32)
        toks[:, :width] = chunks
        past = np.where(live, np.asarray(past_len, np.int32), 0)
        return self._run_paged("verify", toks, lengths.astype(np.int32),
                               past.astype(np.int32), slot_indices, live)

    def _run_paged(self, phase, toks, lens, past, slot_indices, live=None,
                   tables=None):
        """Prepare and run one paged forward call (`phase` is "decode",
        "prefill" or "verify") for the rows of `slot_indices`.

        toks [n, C] the rows' chunks; lens [n] each row's query count
        (real tokens; a decode row always has one); past [n] tokens
        cached before the chunk; live [n] (None = all) — dead rows are
        routed to the trash block and keep their slot state; tables
        [n, nb] the rows' block tables (None = `kv.table_rows`).
        Prefill pads the rows to `prefill_rows` with dead rows.

        Returns the call's logits, plus the expert counts for decode
        and verify."""
        from repro.kernels.paged_attention import active_block_width

        kv, bs = self.kv, self.kv.block_size
        n, chunk = toks.shape
        rows = self.prefill_rows if phase == "prefill" else n
        if tables is None:
            tables = kv.table_rows(slot_indices)
        live_rows = np.zeros(rows, bool)
        live_rows[:n] = True if live is None else np.asarray(live, bool)

        def pad(a):
            return np.concatenate([a, np.zeros((rows - n,) + a.shape[1:], a.dtype)])

        toks, lens, past = pad(toks), pad(lens), pad(past)
        # the rows' last queries fix the table width the call reads: a
        # pow2 bucket, so at most log2(blocks_per_slot) widths compile
        last = past + lens - 1
        mx = int(last[live_rows].max()) if live_rows.any() else 0
        width = active_block_width(mx, bs, max(1, kv.blocks_per_slot))
        {"decode": self.decode_table_widths,
         "prefill": self.prefill_table_widths,
         "verify": self.verify_table_widths}[phase].add(width)
        # dead and pad rows still write their (garbage) K/V: they point
        # at the trash block, so a just-completed slot can never corrupt
        # its own (possibly shared / radix-indexed) blocks
        tab = np.full((rows, width), kv.trash, np.int32)
        tab[:n] = np.asarray(tables, np.int32)[:, :width]
        tab[~live_rows] = kv.trash
        if kv.sanitizer is not None:
            # every block the call writes — [past, past + len) of each
            # live row — must be private; dead rows must be all-trash
            bids, mask = [], []
            for j in range(rows):
                span = (tab[j, past[j] // bs:-(-(past[j] + lens[j]) // bs)]
                        if live_rows[j] else tab[j])
                bids.extend(span.tolist())
                mask.extend([bool(live_rows[j])] * len(span))
            kv.sanitizer.check_scatter_targets(bids, mask)
        self._count_kv_walk(last, width, chunk)
        args = (self.params, jnp.asarray(toks))
        if phase == "decode":
            idx = jnp.asarray(slot_indices, jnp.int32)
            logits, kv.pools, row_states, counts = self._step_paged(
                *args, kv.pools, kv.slot_state, jnp.asarray(tab), idx,
                jnp.asarray(past), self.tiered, jnp.asarray(live_rows),
            )
        else:
            fn = self._prefill_paged if phase == "prefill" else self._verify_paged
            logits, kv.pools, row_states, counts = fn(
                *args, jnp.asarray(lens), jnp.asarray(past), jnp.asarray(tab),
                kv.pools, self.tiered,
            )
        keep = np.flatnonzero(live_rows)
        if len(keep) and jax.tree.leaves(row_states):
            # recurrent state: live rows only, so a dead row's garbage
            # never lands in its slot
            kv.slot_state = scatter_slots(
                kv.slot_state, gather_slots(row_states, keep),
                [int(slot_indices[j]) for j in keep],
            )
        if phase == "prefill":
            self.stats.prefills += n
            self.stats.prefill_tokens += int(lens.sum())
            return logits[:n]
        self._dispatched(rows * chunk)
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
        """Distinct jit compiles of the paged prefill — bounded by
        len(bucket_table) x n_width_buckets(blocks_per_slot), the
        table-width slicing factor; the quantity the CI compile-count
        gate bounds (benchmarks/serving_bench.py)."""
        return int(self._prefill_paged._cache_size())

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

    def _count_kv_walk(self, last, width: int, chunk: int) -> None:
        """Count one paged-attention call's walk (`attn_kv_*`, per
        layer): rows whose last real query sits at `last` (-1 for an
        all-pad row) over a table sliced to `width` columns, `chunk`
        queries a row. The GQA kernel walks P blocks a step and stops at
        each row's last block; the MLA kernel steps one block at a time
        over the whole width."""
        from repro.kernels.paged_attention import group_blocks, kv_walk

        cfg, bs = self.cfg, self.kv.block_size
        if cfg.mla is not None:
            blocks, _ = kv_walk(last, bs, width, 1)
            steps = len(last) * width
        else:
            itemsize = jax.tree.leaves(self.kv.pools)[0].dtype.itemsize
            p = group_blocks(
                bs, width, cfg.n_kv_heads, chunk * (cfg.n_heads // cfg.n_kv_heads),
                cfg.resolved_head_dim, itemsize,
            )
            blocks, steps = kv_walk(last, bs, width, p)
        self.stats.attn_kv_blocks += blocks
        self.stats.attn_kv_steps += steps

    def _dispatched(self, tokens: int) -> None:
        """Record what the MoE step just dispatched ran with."""
        self.last_dispatch = MoEDispatch(tokens, self._applied[0])

    def observe(self, counts: np.ndarray,
                dispatch: Optional[MoEDispatch] = None) -> None:
        """Feed realized per-layer expert loads to the EMA predictor
        (Eq. 8). Runs every step, even under `policy.freeze` — the
        static baseline still reports predictor accuracy.

        With the `dispatch` record of the step that produced `counts`
        (`last_dispatch`, read right after the step), also count what
        the step's MoE layers did (`engine.moe_*`, `expert_rows`,
        `routed_*`), on the host from the counts alone. Counts have one
        column per router output; the predictor and the dispatch
        counters see the held experts' columns, and pairs of experts
        held on other chips count as `routed_elsewhere`."""
        counts = np.asarray(counts)
        if counts.shape[0] != len(self._layer_keys):  # one row per layer
            counts = counts[self._moe_rows]
        held = counts[:, self._held]
        for li in range(len(self._layer_keys)):
            self.predictor.update(li, held[li])
        if dispatch is None:
            return
        st, top_k = self.stats, self.cfg.moe.top_k
        st.moe_steps += 1
        st.moe_assignments += int(held.sum())
        st.experts_touched += int(np.count_nonzero(held))
        st.expert_rows += sum(
            n * tier_capacity(tid, dispatch.tokens, top_k, 1.0)
            for tid, n in enumerate(self._tier_slots)
        )
        by_tier = np.bincount(dispatch.tier.ravel(), weights=counts.ravel(),
                              minlength=AWAY_T + 1)
        st.routed_hot += int(by_tier[HOT])
        st.routed_warm += int(by_tier[WARM])
        st.routed_cold += int(by_tier[COLD])
        st.routed_elsewhere += int(by_tier[AWAY_T])

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
                plan[emitted] = (e + self._held.start, e_tier, e_slot,
                                 dst_tier, v_slot)  # tables: global ids
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
        `plan_migrations`, one call per layer. Fixed-shape plan arrays
        mean exactly one compile of `apply_migrations` per tier-buffer
        structure; each call donates the layer's tier state."""
        if not plans:
            self._unapplied = None
            return
        tr = self._tr
        with tr.span("scheduler.migrate"):
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
            self._applied = _swapped(self._applied, [
                (self._layer_index[key], plan) for key, plan in plans
            ])
        self._unapplied = None

    def replan(self, counts: np.ndarray,
               dispatch: Optional[MoEDispatch] = None) -> None:
        """Synchronous observe + plan + apply in one call (the serving
        loop instead overlaps them with the next step)."""
        self.observe(counts, dispatch)
        self.apply_planned(self.plan_migrations())


def _swapped(tables: tuple, plans: list) -> tuple:
    """[L, E] (expert_tier, expert_slot) after the swaps of `plans`
    ([(layer index, [M, 5] plan)]), row by row as `apply_migrations`
    updates a layer's tables; new arrays, the inputs untouched."""
    tier, slot = tables[0].copy(), tables[1].copy()
    for li, plan in plans:
        t, s = tier[li], slot[li]
        for ea, ta, sa, tb, sb in plan[plan[:, 0] >= 0]:
            occupant = int(np.argmax((t == tb) & (s == sb)))
            t[ea], s[ea] = tb, sb
            t[occupant], s[occupant] = ta, sa
    return tier, slot
