"""KV-cache utilities for the serving engine.

The model's cache pytrees (models.model.init_cache) are ring buffers of
static length; this module adds the bookkeeping the engine needs:
abstract (allocation-free) cache specs for the dry-run, per-arch byte
accounting (the paper offloads the "large KV cache ... to host DIMMs",
§4.1 — on TPU it stays HBM-resident but seq-sharded), and the row
gather / scatter / reset the paged layout's per-slot state uses
(serving/paged_kv.py).

Cache structure convention (init_cache): top-level keys are "layer<i>"
(unrolled prefix layers; leaves carry the batch/slot dim on axis 0) and
"stack" (scanned layers; leaves carry the scan-group dim on axis 0 and
the batch/slot dim on axis 1). All row-level operations here (gather /
scatter / reset) respect that split.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.model import init_cache


def cache_spec(cfg: ModelConfig, batch: int, seq: int):
    """ShapeDtypeStruct pytree of the decode cache (no allocation)."""
    return jax.eval_shape(lambda: init_cache(cfg, batch, seq))


def cache_bytes(cfg: ModelConfig, batch: int, seq: int) -> int:
    spec = cache_spec(cfg, batch, seq)
    return sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(spec)
    )


def _batch_axis(top_key: str) -> int:
    return 1 if top_key == "stack" else 0


def gather_slots(cache, slot_indices):
    """Extract the cache rows of `slot_indices` as a smaller-batch cache
    (the active zigzag group's view). jit-safe: indices may be traced."""
    idx = jnp.asarray(slot_indices, jnp.int32)
    return {
        k: jax.tree.map(lambda a, ax=_batch_axis(k): jnp.take(a, idx, axis=ax), v)
        for k, v in cache.items()
    }


def scatter_slots(cache, sub_cache, slot_indices):
    """Write a gathered (or freshly prefilled) sub-batch cache back into
    the full cache at `slot_indices`. Inverse of gather_slots."""
    idx = jnp.asarray(slot_indices, jnp.int32)

    def put(a, b, ax):
        return a.at[idx].set(b) if ax == 0 else a.at[:, idx].set(b)

    return {
        k: jax.tree.map(lambda a, b, ax=_batch_axis(k): put(a, b, ax), v, sub_cache[k])
        for k, v in cache.items()
    }


def reset_slots(cache, slot_indices):
    """Zero the cache rows of recycled batch slots."""
    idx = jnp.asarray(slot_indices, jnp.int32)

    def zero(a, ax):
        return a.at[idx].set(0) if ax == 0 else a.at[:, idx].set(0)

    return {
        k: jax.tree.map(lambda a, ax=_batch_axis(k): zero(a, ax), v)
        for k, v in cache.items()
    }
