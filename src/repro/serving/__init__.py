from repro.serving.batching import BucketTable, Request, ZigzagBatcher
from repro.serving.engine import (
    TriMoEServingEngine,
    fill_tiers_from_params,
    init_tiered_for_model,
    strip_expert_weights,
)
from repro.serving.kv_cache import (
    cache_bytes,
    cache_spec,
    gather_slots,
    reset_slots,
    scatter_slots,
)
from repro.serving.loop import LoopStats, ServingLoop
from repro.serving.paged_kv import (
    PagedKVCache,
    RadixPrefixIndex,
    init_paged_cache,
    prefix_cacheable,
)
from repro.serving.replay import ReplayResult, replay_requests, requests_from_trace
from repro.serving.tiered_moe import (
    TierSizes,
    apply_migrations,
    init_tiered_state,
    tier_sizes,
    tiered_moe_forward,
)

__all__ = [
    "BucketTable", "Request", "ZigzagBatcher", "TriMoEServingEngine",
    "fill_tiers_from_params", "init_tiered_for_model", "strip_expert_weights",
    "cache_bytes", "cache_spec", "gather_slots", "reset_slots",
    "scatter_slots", "LoopStats", "ServingLoop", "TierSizes",
    "apply_migrations", "init_tiered_state", "tier_sizes", "tiered_moe_forward",
    "PagedKVCache", "RadixPrefixIndex", "init_paged_cache", "prefix_cacheable",
    "ReplayResult", "replay_requests", "requests_from_trace",
]
