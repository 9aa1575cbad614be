"""Continuous-batching TriMoE serving loop (paper §2.2, §4).

The paper's throughput claim rests on amortizing expert-weight movement
over large, continuously refilled decode batches: offline/continuous
batching keeps every decode slot busy, and zigzag batching interleaves
micro-batch groups so the expert relayout for one group overlaps the
other group's step. This module is the orchestration layer above the
engine:

  ServingLoop
    ├─ ZigzagBatcher   — request queue, slot allocation, group rotation
    ├─ SlotKVCache     — slot-managed ring-buffer cache rows
    └─ TriMoEServingEngine — jitted tiered decode / prefill / migration

Per iteration: (1) recycle finished slots (evicting their cache rows)
and admit queued requests — admissions sharing a prompt-length bucket
are padded to the bucket width and prefilled in ONE masked prefill
call that writes each row's cache at its true length and samples the
first token from the per-row last-real-token logits; (2) decode the
active zigzag group at its per-slot positions (fixed group width —
dead slots are masked, so the decode step compiles once); (3) while
that step is in flight on the device, the host replans expert
migrations from the PREVIOUS group's realized loads — the zigzag
overlap of migration and compute; (4) record sampled tokens and rotate
to the next group.

Decoding is greedy and, with the engine default cold_capacity_frac=1.0,
token-for-token identical to single-request generation (verified in
tests/test_serving_loop.py).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.policy import SchedulerPolicy, resolve_policy
from repro.core.tiers import TierThresholds
from repro.models.layers import Params
from repro.obs import resolve_obs
from repro.obs.metrics import RegistryStats, pct
from repro.serving.batching import BucketTable, Request, ZigzagBatcher
from repro.serving.engine import (
    TriMoEServingEngine,
    fill_tiers_from_params,
    init_tiered_for_model,
)
from repro.serving.kv_cache import SlotKVCache
from repro.serving.paged_kv import PagedKVCache
from repro.serving.tiered_moe import TierSizes, tier_sizes


class LoopStats(RegistryStats):
    """Registry-backed serving-loop stats (repro.obs) under the
    `serving.*` prefix. Field access (`stats.admitted += 1`,
    `stats.ttft_s.append(...)`) is source-compatible with the old
    dataclass; `snapshot()` returns the backing registry's one flat
    dict (benchmarks derive their JSON from it).

    Accumulate-vs-reset contract: every metric — including `wall_s` —
    ACCUMULATES across `run()` calls on the same LoopStats. Call
    `reset()` between timed passes (serving_bench does) to start a
    fresh measurement window without detaching from the shared
    registry; binding a fresh `LoopStats()` also works but leaves the
    engine/predictor metrics on the loop's original registry.
    """

    PREFIX = "serving"
    COUNTERS = {
        "admitted": ("requests", "requests admitted into decode slots"),
        "completed": ("requests", "requests fully generated"),
        "decode_steps": ("steps", "group steps that ran the engine"),
        "idle_steps": ("steps", "group rotations finding the group empty"),
        "prefill_chunks": ("calls", "budgeted piggyback chunk calls"),
        "generated_tokens": (
            "tokens", "sampled tokens (prefill firsts + decode)"),
        "util_samples": ("samples", "slot-utilization samples taken"),
        # --- speculative decode (serving/spec_decode.py)
        "spec_steps": ("steps", "decode steps that verified >= 1 draft"),
        "spec_drafted_tokens": (
            "tokens", "draft tokens proposed for verification"),
        "spec_accepted_tokens": (
            "tokens", "draft tokens accepted by the verify chunk"),
        # --- scheduler observability (SchedulerPolicy surface)
        "replans": ("passes", "plan_migrations passes drawn by this loop"),
        "migrations": ("moves", "expert moves those passes emitted"),
        "thrash_events": (
            "events", "tier flip-flops within policy.thrash_window"),
    }
    GAUGES = {
        "wall_s": ("s", "accumulated run() wall time (see reset())"),
        "util_sum": ("", "summed slot-utilization samples"),
        "predictor_accuracy": ("", "EMA tier-prediction accuracy so far"),
    }
    HISTS = {
        "latencies_s": ("s", "per-request admit -> complete latency"),
        # per-request time-to-first-token (submit -> first sampled token)
        "ttft_s": ("s", "time-to-first-token (submit -> first token)"),
        # inter-token latency: gap between a request's consecutive tokens
        "itl_s": ("s", "inter-token latency between consecutive tokens"),
        "plan_s": ("s", "host-side migration-planning latency"),
        # submit -> dispatch of the request's first prefill computation:
        # the batcher queue and the piggyback prefill queue together
        "queue_wait_s": ("s", "submit -> first prefill computation"),
    }

    def __init__(self, registry=None):
        super().__init__(registry)
        for name, fn, unit, desc in (
            ("serving.tokens_per_s", lambda: self.tokens_per_s, "tok/s",
             "generated_tokens / wall_s"),
            ("serving.mean_utilization", lambda: self.mean_utilization, "",
             "mean decode-slot utilization"),
            ("serving.mean_latency_s", lambda: self.mean_latency_s, "s",
             "mean request latency"),
            ("serving.migrations_per_replan",
             lambda: self.migrations_per_replan, "",
             "expert moves per replan pass"),
            ("serving.spec_acceptance_rate",
             lambda: self.spec_acceptance_rate, "",
             "accepted / proposed draft tokens"),
        ):
            self.registry.derived(name, fn, unit=unit, desc=desc,
                                  source="LoopStats")

    @property
    def tokens_per_s(self) -> float:
        return self.generated_tokens / max(self.wall_s, 1e-9)

    @property
    def migrations_per_replan(self) -> float:
        return self.migrations / max(self.replans, 1)

    @property
    def spec_acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens the verify chunk accepted;
        exactly 0.0 before any drafting (no division by zero)."""
        return self.spec_accepted_tokens / max(self.spec_drafted_tokens, 1)

    @property
    def plan_p50_s(self) -> float:
        return self._pct(self.plan_s, 50)

    @property
    def plan_p95_s(self) -> float:
        return self._pct(self.plan_s, 95)

    @property
    def mean_utilization(self) -> float:
        return self.util_sum / max(self.util_samples, 1)

    @property
    def mean_latency_s(self) -> float:
        return float(np.mean(self.latencies_s)) if self.latencies_s else 0.0

    # robust percentile (repro.obs.pct): empty -> 0.0, single sample ->
    # itself, no numpy warnings — kept as a staticmethod for callers
    # that used LoopStats._pct directly
    _pct = staticmethod(pct)

    @property
    def ttft_p50_s(self) -> float:
        return self._pct(self.ttft_s, 50)

    @property
    def ttft_p95_s(self) -> float:
        return self._pct(self.ttft_s, 95)

    @property
    def itl_p50_s(self) -> float:
        return self._pct(self.itl_s, 50)

    @property
    def itl_p95_s(self) -> float:
        return self._pct(self.itl_s, 95)

    def summary(self) -> str:
        return (
            f"{self.completed}/{self.admitted} requests, "
            f"{self.generated_tokens} tokens in {self.wall_s:.2f}s "
            f"({self.tokens_per_s:.1f} tok/s), "
            f"util={self.mean_utilization:.2f}, "
            f"mean_latency={self.mean_latency_s * 1e3:.0f}ms, "
            f"ttft_p95={self.ttft_p95_s * 1e3:.0f}ms "
            f"itl_p95={self.itl_p95_s * 1e3:.0f}ms, "
            f"decode_steps={self.decode_steps} idle_steps={self.idle_steps} "
            f"prefill_chunks={self.prefill_chunks}, "
            f"spec_acc={self.spec_acceptance_rate:.2f} "
            f"({self.spec_accepted_tokens}/{self.spec_drafted_tokens}), "
            f"replans={self.replans} "
            f"migrations={self.migrations} "
            f"({self.migrations_per_replan:.1f}/replan) "
            f"thrash={self.thrash_events} "
            f"plan_p95={self.plan_p95_s * 1e3:.1f}ms "
            f"pred_acc={self.predictor_accuracy:.2f}"
        )


@dataclasses.dataclass
class _PrefillTask:
    """One admitted request's in-flight piggyback prefill: `done` tokens
    of the prompt are already in the cache (radix prefix hit + chunks
    landed so far); the rest streams in budgeted chunks."""

    slot: int
    req: Request
    done: int
    started: bool = False  # a chunk of it has been dispatched


class ServingLoop:
    """Multi-request continuous-batching loop over the TriMoE engine.

    batch_size decode slots are split into n_groups zigzag groups; the
    cache holds batch_size rows of length cache_len (each admitted
    request needs prompt_len + max_new_tokens - 1 <= cache_len to avoid
    ring wrap-around).

    Prefill is LENGTH-BUCKETED by default: `bucket_table` (default
    powers-of-two widths capped at cache_len) pads every admitted
    prompt to its bucket width and batches same-bucket admissions into
    one masked prefill call of up to `prefill_rows` rows, so a
    mixed-length trace compiles the prefill at most len(bucket_table)
    times (engine.prefill_compiles; gated in CI via
    benchmarks/serving_bench.py --mixed). Pass bucket_table=None for
    the legacy exact-length path (one compile per distinct prompt
    length). `max_admit_wait` caps how many admit rounds a partial
    same-bucket cohort may be held back (starvation cap).

    The KV store is PAGED by default (`kv_layout="paged"`,
    serving/paged_kv.py): K/V lives in a pool of `block_size`-token
    blocks addressed through per-slot block tables, admission claims
    the longest radix-cached prefix of each prompt (`prefix_cache`) and
    prefills only the uncached suffix (still bucketed + masked), decode
    allocates blocks on demand, and eviction returns refcount-0 blocks
    to the pool LRU-last so shared prefixes survive across requests.
    `kv_pool_blocks` shrinks the pool below the contiguous reservation
    (`batch_size * ceil(cache_len / block_size)`); the HBM thereby
    reclaimed feeds `tiered_moe.tier_sizes(reclaimed_kv_bytes=...)` —
    more hot-resident experts. `kv_layout="slots"` restores the
    contiguous SlotKVCache.

    Attention against the pools is BLOCK-SPARSE in BOTH phases: the
    engine slices each decode step's AND each prefill chunk's block
    tables to the pow2-bucketed active width, and `paged_attn_backend`
    ("auto" | "pallas" | "ref", default the config's setting) picks the
    chunked Pallas paged-attention kernel family
    (kernels/paged_attention — decode is the chunk-of-1 case) or the
    jnp dense-gather path. `moe_backend` is the same knob for the
    expert-FFN hot path (kernels/moe_gemm grouped GEMM for prefill
    buffers, kernels/expert_gemv batched GEMV for decode buffers, or
    the einsum reference); both resolve through the one
    `kernels/backend.py` rule and land in the config the engine's
    jitted step closures capture.

    Admission prefill is CHUNKED and PIGGYBACKED by default
    (`chunked_prefill=True`, paged layout + attention-only archs): an
    admitted prompt's uncached suffix streams into the cache in chunks
    of at most `prefill_chunk_tokens` tokens per loop iteration (chunk
    widths drawn from the bucket table, past-widths from the same pow2
    table slicing as decode), each chunk sharing the iteration with a
    decode group step — so a long prompt never stalls in-flight decode
    behind one monolithic prefill call (the TTFT/ITL win
    `serving_bench.py --mixed` measures). The slot joins decode once
    its last chunk lands and samples the first token. Recurrent-mixer
    archs (chunk state cannot be threaded through a token-keyed cache)
    and the contiguous `kv_layout="slots"` fall back to whole-suffix
    admission prefill.

    SPECULATIVE DECODE (`spec_decode=True`, serving/spec_decode.py):
    each decode step drafts up to `spec_config.k` tokens per live slot
    (prompt-lookup: radix prefix index first, per-slot suffix n-grams
    second — no draft model, no RNG) and verifies the chunk
    [sampled token, drafts...] through the SAME chunked paged kernels
    as one `engine.verify_slots_paged` call. The greedy accept-prefix
    rule commits every draft that matches the verify argmax plus one
    bonus token; rejected suffixes roll back via
    `PagedKVCache.truncate` (refcount/COW-aware, sanitizer-validated).
    Greedy token streams are IDENTICAL to non-speculative serving at
    fp32: a chunk of 1 is bitwise the decode step (same kernel) and
    wider chunks agree to fp32 rounding with exactly equal argmax
    tokens; throughput multiplies by the acceptance rate on
    repetitive/replayed traffic.
    Requires the paged layout + an attention-only arch (same gate as
    chunked prefill). Acceptance stats land on the shared registry
    (`serving.spec_*`, `serving.spec_acceptance_rate`).

    OBSERVABILITY (repro.obs): `obs=` accepts an `Observability` (share
    a registry/tracer) or an `ObsConfig`, resolved with the same
    precedence rule as `scheduler=`: explicit kwarg > `cfg.obs` >
    defaults (metrics on, tracing off). The loop, engine, and predictor
    register their stats on ONE registry (`loop.stats.snapshot()` shows
    all three) and, with `ObsConfig(trace=True)`, emit nested spans +
    the tier timeline to one tracer — export with
    `loop.obs.export_trace(path)` or tools/export_trace.py.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Params,
        tiered: Optional[Params] = None,
        *,
        batch_size: int = 8,
        n_groups: int = 1,
        cache_len: int = 64,
        sizes: Optional[TierSizes] = None,
        plan_size: Optional[int] = None,  # DEPRECATED -> scheduler=
        thresholds: Optional[TierThresholds] = None,  # DEPRECATED -> scheduler=
        cold_capacity_frac: float = 1.0,
        rng_seed: int = 1,
        bucket_table: "BucketTable | None | str" = "auto",
        prefill_rows: Optional[int] = None,
        max_admit_wait: int = 4,
        kv_layout: str = "paged",
        block_size: int = 4,
        kv_pool_blocks: Optional[int] = None,
        prefix_cache: bool = True,
        kv_sanitize: Optional[bool] = None,  # None -> $REPRO_KV_SANITIZE
        paged_attn_backend: Optional[str] = None,
        moe_backend: Optional[str] = None,
        chunked_prefill: bool = True,
        prefill_chunk_tokens: Optional[int] = None,
        spec_decode: bool = False,
        spec_config=None,  # DraftConfig | None (serving/spec_decode.py)
        scheduler: Optional[SchedulerPolicy] = None,
        obs=None,  # Observability | ObsConfig | None (repro.obs)
    ):
        assert cfg.moe is not None, "ServingLoop drives the TriMoE MoE path"
        assert kv_layout in ("paged", "slots"), kv_layout
        if paged_attn_backend is not None:
            cfg = dataclasses.replace(cfg, paged_attn_backend=paged_attn_backend)
        if moe_backend is not None:
            cfg = dataclasses.replace(cfg, moe_backend=moe_backend)
        # one resolution rule for the scheduling knobs, mirroring the
        # kernel-backend pattern: explicit scheduler= > cfg.scheduler >
        # defaults; the bare plan_size=/thresholds= kwargs fold in with a
        # DeprecationWarning (honored one release)
        self.policy = resolve_policy(
            cfg, scheduler, plan_size=plan_size, thresholds=thresholds,
            caller="ServingLoop",
        )
        cfg = dataclasses.replace(cfg, scheduler=self.policy)
        # observability resolves the same way: explicit obs= > cfg.obs >
        # defaults (metrics on, tracing off). One Observability bundle —
        # registry + tracer — is shared with the engine and predictor,
        # so loop/engine/predictor metrics land on ONE snapshot and all
        # spans sit on one timeline.
        self.obs = resolve_obs(cfg, obs, caller="ServingLoop")
        self._tr = self.obs.tracer
        self.cfg = cfg
        self.paged = kv_layout == "paged"
        from repro.serving.paged_kv import prefix_cacheable

        # chunked piggyback needs a token-position-addressable cache for
        # EVERY mixer (a chunk boundary cannot thread recurrent state)
        self.chunked = (
            chunked_prefill and self.paged and prefix_cacheable(cfg)
        )
        if self.paged:
            self.kv = PagedKVCache(
                cfg, batch_size, cache_len, block_size=block_size,
                n_blocks=kv_pool_blocks, prefix_cache=prefix_cache,
                sanitize=kv_sanitize,
            )
            reclaimed = self.kv.reclaimed_bytes(cache_len)
        else:
            self.kv = SlotKVCache(cfg, batch_size, cache_len)
            reclaimed = 0
        if tiered is None:
            import jax

            if sizes is None:
                sizes = (
                    tier_sizes(cfg, reclaimed_kv_bytes=reclaimed)
                    if self.paged else _default_sizes(cfg)
                )
            tiered = init_tiered_for_model(jax.random.PRNGKey(rng_seed), cfg, sizes)
            tiered = fill_tiers_from_params(params, tiered, cfg)
        if bucket_table == "auto":
            bucket_table = BucketTable.powers_of_two(cache_len)
        self.bucket_table = bucket_table
        self.batcher = ZigzagBatcher(
            batch_size, n_groups, bucket_table=bucket_table,
            max_admit_wait=max_admit_wait,
        )
        self.engine = TriMoEServingEngine(
            cfg, params, self.kv, tiered, sizes=sizes,
            cold_capacity_frac=cold_capacity_frac,
            prefill_rows=prefill_rows or min(batch_size, 4),
            scheduler=self.policy, obs=self.obs,
        )
        # budgeted suffix tokens per piggyback chunk call: the bound on
        # how long any single prefill call can stall decode. 32 balances
        # per-call dispatch overhead against interleaving granularity;
        # lower it for tighter ITL, raise it for prompt throughput.
        if prefill_chunk_tokens is None:
            prefill_chunk_tokens = 32
        assert prefill_chunk_tokens >= 1
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # speculative multi-token decode: verify-through-the-chunked-
        # kernels needs the paged layout and a token-position-
        # addressable cache for every mixer (same gate as chunked
        # prefill — truncate cannot roll back recurrent state)
        self.spec = bool(spec_decode)
        if self.spec:
            assert self.paged and prefix_cacheable(cfg), (
                "spec_decode requires kv_layout='paged' and an "
                "attention-only arch (chunk-of-k verification and "
                "rollback go through the paged block pools)"
            )
            from repro.serving.spec_decode import PromptLookupDrafter

            self.drafter = PromptLookupDrafter(
                spec_config, radix=self.kv.radix
            )
        else:
            self.drafter = None
        self.stats = LoopStats(self.obs.registry)
        self.completions: List[Request] = []
        self._t_admit: Dict[int, float] = {}
        self._t_submit: Dict[int, float] = {}
        self._t_last_tok: Dict[int, float] = {}
        self._slot_req: Dict[int, Request] = {}  # paged: slot -> request
        self._prefill_tasks: List[_PrefillTask] = []  # FIFO piggyback queue
        # previous group's realized loads and its engine.last_dispatch
        self._pending_counts = None
        self._planned: list = []  # plans drawn but not yet applied
        self._steps_since_replan = 0  # policy.replan_every cadence

    # ------------------------------------------------------------ intake
    def submit(self, req: Request) -> None:
        assert req.prompt_len + req.max_new_tokens - 1 <= self.kv.seq_len, (
            f"request {req.rid}: {req.prompt_len}+{req.max_new_tokens} tokens "
            f"overflow the cache ring (cache_len={self.kv.seq_len})"
        )
        # keyed by rid: a re-used rid (bench warmup/timed passes) must
        # restart the TTFT clock, so overwrite rather than setdefault
        self._t_submit[req.rid] = time.time()
        self.batcher.submit(req)

    def _free_slots(self, freed: List[int]) -> None:
        """Evict finished requests' KV: paged slots index their full
        (prompt + generated) blocks for future prefix hits before the
        refcounts drop; contiguous slots zero their rows."""
        if not freed:
            return
        if not self.paged:
            self.kv.free(freed)
            return
        for i in freed:
            r = self._slot_req.pop(i, None)
            if self.drafter is not None:
                self.drafter.free_slot(i)
            # index prompt + generated[:-1]: the FINAL sampled token was
            # never fed back through decode, so its K/V does not exist —
            # a block "completed" by it must not enter the radix
            toks = (
                None if r is None
                else np.concatenate([np.asarray(r.prompt, np.int32),
                                     np.asarray(r.generated[:-1], np.int32)])
            )
            self.kv.free_slot(i, tokens=toks)

    def _admit(self) -> None:
        with self._tr.span("serving.admit"):
            self._admit_inner()

    def _prefill_dispatched(self, reqs) -> None:
        """`reqs` have their first prefill computation dispatched now."""
        now = time.time()
        for r in reqs:
            t0 = self._t_submit.get(r.rid)
            if t0 is not None:
                self.stats.queue_wait_s.append(now - t0)

    def _admit_inner(self) -> None:
        freed, filled = self.batcher.admit()
        self._drain_completed()
        self._free_slots(freed)
        past_len: Dict[int, int] = {}
        for i in filled:
            r = self.batcher.slots[i].request
            if self.paged:
                # prefix-match on admission: claim the longest cached
                # prefix, allocate fresh blocks for the uncached rest
                past_len[i] = self.kv.admit_slot(i, r.prompt)
                self._slot_req[i] = r
                if self.drafter is not None:
                    self.drafter.begin_slot(i, r.prompt)
            else:
                self.kv.claim(i)
            self._t_admit[r.rid] = time.time()
            self.stats.admitted += 1
        if not filled:
            return
        if self.chunked:
            # piggyback admission: don't prefill here — enqueue the
            # uncached suffix as budgeted chunk work that `run` drains
            # one chunk call per iteration, alongside decode steps
            for i in filled:
                r = self.batcher.slots[i].request
                self.batcher.slots[i].prefilling = True
                self._prefill_tasks.append(
                    _PrefillTask(i, r, past_len.get(i, 0))
                )
            return
        # prefill writes the slots' cache (rows or blocks) in place; the
        # per-row logits sample the first generated token (no wasted
        # re-decode of the last prompt token). Prompt-token accounting
        # lives in engine.stats.prefill_tokens.
        if not self.paged and self.bucket_table is None:
            for i in filled:  # legacy exact-length path
                r = self.batcher.slots[i].request
                self._prefill_dispatched([r])
                logits = self.engine.prefill_slots(r.prompt[None, :], [i])
                self._record_first(r, logits[0])
            return
        # batch same-bucket admissions into one padded masked prefill;
        # under the paged layout rows are keyed by their UNCACHED suffix
        # length — a prefix hit moves the request to a smaller bucket
        groups: Dict[int, List[int]] = {}
        for i in filled:
            r = self.batcher.slots[i].request
            n_new = r.prompt_len - past_len.get(i, 0)
            key = (
                n_new if self.bucket_table is None
                else self.bucket_table.bucket_of(n_new)
            )
            groups.setdefault(key, []).append(i)
        for width, slots in sorted(groups.items()):
            prompts = np.zeros((len(slots), width), np.int32)
            lengths = np.zeros((len(slots),), np.int32)
            pasts = np.zeros((len(slots),), np.int32)
            for row, i in enumerate(slots):
                r = self.batcher.slots[i].request
                pasts[row] = past_len.get(i, 0)
                suffix = r.prompt[pasts[row]:]
                prompts[row, : len(suffix)] = suffix
                lengths[row] = len(suffix)
            self._prefill_dispatched(
                [self.batcher.slots[i].request for i in slots])
            if self.paged:
                logits = self.engine.prefill_slots_paged(
                    prompts, slots, lengths, pasts
                )
                for i in slots:
                    # index the freshly computed prompt blocks so later
                    # (and queued) admissions can share them
                    self.kv.commit_prompt(i, self.batcher.slots[i].request.prompt)
            else:
                logits = self.engine.prefill_slots(prompts, slots, lengths=lengths)
            for row, i in enumerate(slots):
                self._record_first(
                    self.batcher.slots[i].request, logits[row], slot=i
                )

    def _prefill_step(self) -> None:
        """Run at most ONE budgeted chunk call of pending piggyback
        prefill work. Each loop iteration gets one of these plus one
        decode group step, so a long admitted prompt streams into the
        cache at `prefill_chunk_tokens` tokens per iteration while
        decode keeps producing tokens. Serviced-but-unfinished tasks
        rotate to the back of the queue (round-robin), so a long prompt
        neither head-blocks short admissions' first tokens nor starves
        behind them. A task whose last chunk lands samples its first
        token from that chunk's last-real-token logits, radix-commits
        the prompt, and rejoins decode."""
        if not self._prefill_tasks:
            return
        with self._tr.span("serving.prefill_chunk"):
            self._prefill_chunk()

    def _prefill_chunk(self) -> None:
        rows: List[tuple] = []  # (task, chunk size)
        left = self.prefill_chunk_tokens
        for t in self._prefill_tasks:
            if left <= 0 or len(rows) >= self.engine.prefill_rows:
                break
            # a task's natural chunk is min(remaining, budget); FIFO
            # followers join the call only if their whole chunk fits the
            # leftover budget — co-scheduling must not shrink chunks
            # (that would split short prompts into confetti)
            n = min(t.req.prompt_len - t.done, self.prefill_chunk_tokens)
            if rows and n > left:
                break
            rows.append((t, n))
            left -= n
        width = max(n for _, n in rows)
        if self.bucket_table is not None:
            width = self.bucket_table.bucket_of(width)
        prompts = np.zeros((len(rows), width), np.int32)
        lengths = np.zeros((len(rows),), np.int32)
        pasts = np.zeros((len(rows),), np.int32)
        slots = []
        for row, (t, n) in enumerate(rows):
            prompts[row, :n] = t.req.prompt[t.done:t.done + n]
            lengths[row] = n
            pasts[row] = t.done
            slots.append(t.slot)
        self._prefill_dispatched([t.req for t, _ in rows if not t.started])
        for t, _ in rows:
            t.started = True
        logits = self.engine.prefill_slots_paged(prompts, slots, lengths, pasts)
        self.stats.prefill_chunks += 1
        unfinished = []
        for row, (t, n) in enumerate(rows):
            t.done += n
            if t.done == t.req.prompt_len:
                self.batcher.slots[t.slot].prefilling = False
                # index the freshly computed prompt blocks so later
                # (and queued) admissions can share them
                self.kv.commit_prompt(t.slot, t.req.prompt)
                self._record_first(t.req, logits[row], slot=t.slot)
            else:
                unfinished.append(t)
        # rows is a prefix of the task queue; rotate its survivors back
        self._prefill_tasks = self._prefill_tasks[len(rows):] + unfinished

    def _record_first(self, r: Request, row_logits,
                      slot: Optional[int] = None) -> None:
        with self._tr.span("serving.sync"):
            tok = int(np.asarray(jnp.argmax(row_logits, -1)))
        r.generated.append(tok)
        if self.drafter is not None and slot is not None:
            self.drafter.extend(slot, [tok])
        self.stats.generated_tokens += 1
        now = time.time()
        t0 = self._t_submit.get(r.rid, self._t_admit.get(r.rid))
        if t0 is not None:
            self.stats.ttft_s.append(now - t0)
        self._t_last_tok[r.rid] = now

    def _drain_completed(self) -> None:
        while len(self.completions) < len(self.batcher.completed):
            r = self.batcher.completed[len(self.completions)]
            self.completions.append(r)
            self.stats.completed += 1
            t0 = self._t_admit.get(r.rid)
            if t0 is not None:
                self.stats.latencies_s.append(time.time() - t0)
            # per-rid timing state must not grow without bound in a
            # long-lived loop serving a stream of unique rids
            for d in (self._t_admit, self._t_submit, self._t_last_tok):
                d.pop(r.rid, None)

    # ------------------------------------------------------------- drive
    def _work_remaining(self) -> bool:
        if self.batcher.queue or self._prefill_tasks:
            return True
        return any(
            s.request is not None and not s.request.done for s in self.batcher.slots
        )

    def _flush_replan(self) -> None:
        """The double-buffered relayout flush, called right after a step
        (or idle rotation) is dispatched:

          1. APPLY the plans drawn during the previous iteration — the
             jitted weight swaps overlap the step that is now in flight
             (host-side analogue of double-buffered relayout);
          2. OBSERVE the previous group's realized loads into the EMA
             predictor (every step);
          3. every `policy.replan_every` observed steps, DRAW the next
             plans — applied at the next flush, one iteration later.
        """
        eng = self.engine
        if self._planned:
            eng.apply_planned(self._planned)
            self._planned = []
        if self._pending_counts is None:
            return
        counts, dispatch = self._pending_counts
        self._pending_counts = None
        eng.observe(counts, dispatch)
        self._steps_since_replan += 1
        if self._steps_since_replan < self.policy.replan_every:
            return
        self._steps_since_replan = 0
        st, es = self.stats, eng.stats
        thrash_before = es.thrash_events
        with self._tr.span("scheduler.replan"):
            self._planned = eng.plan_migrations()
        st.replans += 1
        st.migrations += sum(
            int((plan[:, 0] >= 0).sum()) for _, plan in self._planned
        )
        st.thrash_events += es.thrash_events - thrash_before
        st.plan_s.append(es.plan_latency_s[-1])
        st.predictor_accuracy = eng.predictor.stats.accuracy

    def step_once(self) -> None:
        """One scheduling iteration: admit, one piggyback prefill chunk,
        one zigzag-group decode step, then the replan flush. Public so a
        trace replay driver (serving/replay.py) can interleave arrivals
        at exact loop iterations; call `finish()` when done.

        With tracing enabled (repro.obs) each iteration is one nested
        span tree: serving.step > {serving.admit, serving.prefill_chunk,
        serving.decode > {scheduler.replan, scheduler.migrate,
        serving.sync}} — the "where did this step's time go" view, on
        the device trace's clock under the JAX profiler."""
        tr = self._tr
        with tr.span("serving.step"):
            self._admit()
            # piggyback: one budgeted prefill chunk rides along with
            # this iteration's decode step (chunked_prefill)
            self._prefill_step()
            gb = self.batcher.next_group()
            self.stats.util_sum += self.batcher.utilization
            self.stats.util_samples += 1
            if gb is None:
                # the active group is idle — use its step slot for any
                # outstanding migration work instead
                self.stats.idle_steps += 1
                self._flush_replan()
                return
            _, idxs, toks, pos, live = gb
            if self.spec:
                with tr.span("serving.decode"):
                    self._spec_step(idxs, toks, pos, live)
                return
            with tr.span("serving.decode"):
                if self.paged:
                    for row, i in enumerate(idxs):
                        if live[row]:
                            # on-demand block alloc at block boundaries,
                            # copy-on-write if the tail block is shared
                            self.kv.ensure_block(i, int(pos[row]))
                    logits, counts = self.engine.step_slots_paged(
                        toks, pos, idxs, self.kv.table_rows(idxs), live=live
                    )
                else:
                    logits, counts = self.engine.step_slots(
                        toks, pos, idxs, live=live
                    )
                # zigzag overlap: while this group's step runs on the
                # device, the host applies + replans migrations from
                # previous loads
                self._flush_replan()
                self._pending_counts = (counts, self.engine.last_dispatch)
                with tr.span("serving.sync"):
                    nxt = np.asarray(jnp.argmax(logits, -1))
            live_idx = [i for i, alive in zip(idxs, live) if alive]
            self.batcher.record(live_idx, nxt[live])
            self.stats.decode_steps += 1
            self.stats.generated_tokens += len(live_idx)
            now = time.time()
            for i in live_idx:
                rid = self.batcher.slots[i].request.rid
                prev = self._t_last_tok.get(rid)
                if prev is not None:
                    self.stats.itl_s.append(now - prev)
                self._t_last_tok[rid] = now

    def _spec_step(self, idxs, toks, pos, live) -> None:
        """Speculative decode of one zigzag group: draft per slot,
        verify all chunks in ONE chunk-of-k engine call, greedy
        accept-prefix, rollback rejected tails.

        Per live row the chunk is [this step's input token, draft_1..]:
        position i's verify logits condition on chunk tokens 0..i plus
        the cached prefix, so argmax at position i is EXACTLY what
        sequential greedy decode would sample after draft i — comparing
        it against draft i+1 (accept-prefix) and committing the first
        mismatch position's argmax as the bonus token reproduces the
        sequential stream token-for-token (a row with no drafts is the
        chunk-of-1 case, i.e. a plain decode step). Accepted positions
        keep the K/V the verify scattered; rejected tails roll back via
        `PagedKVCache.truncate` (block refs dropped, shared/radix tail
        COW-detached) so the next step's scatter targets stay clean."""
        st = self.stats
        tr = self._tr
        drafts: List[List[int]] = [[] for _ in idxs]
        with tr.span("spec.draft"):
            for row, i in enumerate(idxs):
                if not live[row]:
                    continue
                r = self.batcher.slots[i].request
                # cap: the commit may add at most `remaining` tokens
                # (accepted drafts + bonus), and every chunk position
                # must fit the slot's block table
                cap = min(
                    r.max_new_tokens - len(r.generated) - 1,
                    self.kv.seq_len - 1 - int(pos[row]),
                )
                if cap > 0:
                    drafts[row] = self.drafter.draft(i, cap)
        n_drafted = sum(len(d) for d in drafts)
        width = 1 + max(len(d) for d in drafts)
        chunk = np.zeros((len(idxs), width), np.int32)
        lens = np.zeros((len(idxs),), np.int32)
        for row, i in enumerate(idxs):
            if not live[row]:
                continue
            row_toks = [int(toks[row, 0])] + drafts[row]
            chunk[row, : len(row_toks)] = row_toks
            lens[row] = len(row_toks)
            for p in range(int(pos[row]), int(pos[row]) + len(row_toks)):
                # on-demand alloc + COW for every chunk position (the
                # same contract as plain decode, k+1 positions at once)
                self.kv.ensure_block(i, p)
        with tr.span("spec.verify"):
            logits, counts = self.engine.verify_slots_paged(
                chunk, idxs, lens, pos, live=live
            )
            # zigzag overlap, exactly like the plain decode step
            self._flush_replan()
            self._pending_counts = (counts, self.engine.last_dispatch)
            with tr.span("serving.sync"):
                nxt = np.asarray(jnp.argmax(logits, -1))  # [W, Kp]
        st.decode_steps += 1
        if n_drafted:
            st.spec_steps += 1
            st.spec_drafted_tokens += n_drafted
        now = time.time()
        for row, i in enumerate(idxs):
            if not live[row]:
                continue
            r = self.batcher.slots[i].request
            d = drafts[row]
            a = 0
            while a < len(d) and int(nxt[row, a]) == d[a]:
                a += 1
            commit = d[:a] + [int(nxt[row, a])]
            st.spec_accepted_tokens += a
            # multi-token commit: extend the request + slot cursor by
            # hand (ZigzagBatcher.record is one-token), then roll the
            # cache back to the committed length — the bonus token's
            # K/V does not exist yet, exactly as after a plain step
            r.generated.extend(commit)
            self.batcher.slots[i].pos += len(commit)
            self.kv.truncate(i, int(pos[row]) + len(commit))
            self.drafter.extend(i, commit)
            st.generated_tokens += len(commit)
            rid = r.rid
            prev = self._t_last_tok.get(rid)
            if prev is not None:
                # spread the step's gap over its committed tokens so
                # ITL percentiles stay comparable with plain decode
                gap = (now - prev) / len(commit)
                for _ in commit:
                    st.itl_s.append(gap)
            self._t_last_tok[rid] = now

    def finish(self) -> None:
        """Settle all deferred scheduling work (observe + plan + apply)
        and recycle the final wave of completions, leaving the loop
        reusable for further submissions."""
        self._flush_replan()
        if self._planned:
            self.engine.apply_planned(self._planned)
            self._planned = []
        # recycle (but don't admit) the final wave of completions so the
        # loop can be reused for further submissions
        self._free_slots(self.batcher.recycle())
        self._drain_completed()

    def run(self, max_steps: Optional[int] = None) -> List[Request]:
        """Drive until every submitted request completes (or max_steps
        group rotations elapse). Returns the completed requests in
        completion order; per-request tokens are in Request.generated.
        wall_s — like every LoopStats metric — ACCUMULATES across run()
        calls; call `self.stats.reset()` between timed passes (as
        serving_bench does) to start a fresh window."""
        t_start = time.time()
        steps = 0
        while self._work_remaining():
            if max_steps is not None and steps >= max_steps:
                break
            steps += 1
            self.step_once()
        self.finish()
        self.stats.wall_s += time.time() - t_start
        return self.completions


def _default_sizes(cfg: ModelConfig) -> TierSizes:
    """Example-scale tier split: ~25% hot, ~30% warm, rest cold."""
    e = cfg.moe.n_experts
    n_hot = max(1, e // 4)
    n_warm = max(1, int(0.3 * e))
    return TierSizes(n_hot, n_warm, e - n_hot - n_warm)
