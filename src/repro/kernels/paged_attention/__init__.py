from repro.kernels.paged_attention.ops import (
    active_block_width,
    n_width_buckets,
    resolve_backend,
)
from repro.kernels.paged_attention.paged_attention import (
    group_blocks,
    kv_walk,
    lane_view,
    paged_decode_gqa,
    paged_decode_mla,
    paged_prefill_gqa,
    paged_prefill_mla,
)
from repro.kernels.paged_attention.ref import (
    paged_decode_gqa_ref,
    paged_decode_mla_ref,
    paged_prefill_gqa_ref,
    paged_prefill_mla_ref,
)

__all__ = [
    "resolve_backend",
    "active_block_width",
    "n_width_buckets",
    "group_blocks",
    "kv_walk",
    "lane_view",
    "paged_decode_gqa",
    "paged_decode_mla",
    "paged_decode_gqa_ref",
    "paged_decode_mla_ref",
    "paged_prefill_gqa",
    "paged_prefill_mla",
    "paged_prefill_gqa_ref",
    "paged_prefill_mla_ref",
]
