"""Paged KV-cache subsystem tests (serving/paged_kv.py).

Three layers of evidence:

  * block-table attention == contiguous attention, for random block
    layouts and lengths (deterministic sweep always runs; a hypothesis
    property version widens the search when hypothesis is installed);
  * host bookkeeping units: radix prefix match/insert, LRU leaf-first
    eviction, refcounts, on-demand allocation, copy-on-write;
  * the flagship serving invariant: shared-prefix admission (radix hit,
    suffix-only prefill) is token-for-token identical to cold
    admission.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models import attention as attn
from repro.models.model import decode_step, init_params, prefill
from repro.serving.batching import Request
from repro.serving.loop import ServingLoop
from repro.serving.paged_kv import (
    PagedKVCache,
    RadixPrefixIndex,
    prefix_cacheable,
)
from repro.serving.tiered_moe import tier_sizes

GQA_ARCH = "granite-moe-1b-a400m"
MLA_ARCH = "deepseek-v2-236b"


@pytest.fixture(scope="module")
def gqa_setup():
    cfg = reduce_for_smoke(get_config(GQA_ARCH))
    return cfg, attn.init_gqa(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def mla_setup():
    cfg = reduce_for_smoke(get_config(MLA_ARCH))
    return cfg, attn.init_mla(jax.random.PRNGKey(0), cfg)


def _random_layout(rng, b, seq, bs):
    """Random injective block tables + the contiguous->pool scatter."""
    nb = seq // bs
    n_blocks = b * nb
    perm = rng.permutation(n_blocks)
    tables = perm.reshape(b, nb).astype(np.int32)
    return nb, n_blocks, tables


def _blockify(rng, contiguous, tables, bs, n_blocks):
    """Copy a contiguous [B, S, ...] cache into a pool [N+1, bs, ...]
    laid out by `tables`; unreferenced pool cells get garbage to prove
    the position masks cover them."""
    b, s = contiguous.shape[:2]
    pool = rng.normal(size=(n_blocks + 1, bs, *contiguous.shape[2:]))
    pool = pool.astype(np.asarray(contiguous).dtype)
    for row in range(b):
        for j, bid in enumerate(tables[row]):
            pool[bid] = np.asarray(contiguous[row, j * bs:(j + 1) * bs])
    return jnp.asarray(pool)


def _gqa_case(cfg, p, rng, lengths, bs, seq):
    b = len(lengths)
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)), jnp.float32)
    cache_k = jnp.asarray(rng.normal(size=(b, seq, kv, hd)), jnp.float32)
    cache_v = jnp.asarray(rng.normal(size=(b, seq, kv, hd)), jnp.float32)
    pos = np.asarray(lengths, np.int32)  # decode the next position
    ref_o, ref_k, ref_v = attn.gqa_decode(p, cfg, x, cache_k, cache_v, pos)

    nb, n_blocks, tables = _random_layout(rng, b, seq, bs)
    pool_k = _blockify(rng, cache_k, tables, bs, n_blocks)
    pool_v = _blockify(rng, cache_v, tables, bs, n_blocks)
    out, pool_k, pool_v = attn.gqa_prefill_paged(  # decode: chunk of 1
        p, cfg, x, pool_k, pool_v, jnp.asarray(tables), pos, pos[:, None],
        None,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_o, np.float32),
        rtol=1e-5, atol=1e-5,
    )
    # the new token's K/V landed in the right block cell
    for row in range(b):
        t = int(pos[row])
        bid, off = tables[row][t // bs], t % bs
        np.testing.assert_allclose(
            np.asarray(pool_k[bid, off], np.float32),
            np.asarray(ref_k[row, t], np.float32), rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(pool_v[bid, off], np.float32),
            np.asarray(ref_v[row, t], np.float32), rtol=1e-5, atol=1e-5,
        )


def _mla_case(cfg, p, rng, lengths, bs, seq):
    b = len(lengths)
    m = cfg.mla
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)), jnp.float32)
    cache_ckv = jnp.asarray(
        rng.normal(size=(b, seq, m.kv_lora_rank)), jnp.float32
    )
    cache_kr = jnp.asarray(
        rng.normal(size=(b, seq, m.qk_rope_head_dim)), jnp.float32
    )
    pos = np.asarray(lengths, np.int32)
    ref_o, ref_c, ref_r = attn.mla_decode(p, cfg, x, cache_ckv, cache_kr, pos)

    nb, n_blocks, tables = _random_layout(rng, b, seq, bs)
    pool_c = _blockify(rng, cache_ckv, tables, bs, n_blocks)
    pool_r = _blockify(rng, cache_kr, tables, bs, n_blocks)
    out, pool_c, pool_r = attn.mla_prefill_paged(  # decode: chunk of 1
        p, cfg, x, pool_c, pool_r, jnp.asarray(tables), pos, pos[:, None],
        None,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_o, np.float32),
        rtol=1e-5, atol=1e-5,
    )
    for row in range(b):
        t = int(pos[row])
        bid, off = tables[row][t // bs], t % bs
        np.testing.assert_allclose(
            np.asarray(pool_c[bid, off], np.float32),
            np.asarray(ref_c[row, t], np.float32), rtol=1e-5, atol=1e-5,
        )


def test_paged_gqa_decode_matches_contiguous(gqa_setup):
    cfg, p = gqa_setup
    rng = np.random.default_rng(0)
    for seed in range(4):
        rng = np.random.default_rng(seed)
        bs = int(rng.choice([2, 4, 8]))
        seq = 16
        lengths = rng.integers(0, seq - 1, size=3)
        _gqa_case(cfg, p, rng, lengths, bs, seq)


def test_paged_mla_decode_matches_contiguous(mla_setup):
    cfg, p = mla_setup
    rng = np.random.default_rng(1)
    for seed in range(3):
        rng = np.random.default_rng(100 + seed)
        bs = int(rng.choice([2, 4]))
        seq = 8
        lengths = rng.integers(0, seq - 1, size=2)
        _mla_case(cfg, p, rng, lengths, bs, seq)


def test_paged_attention_property_random_layouts(gqa_setup):
    """Hypothesis widening of the deterministic sweep: any lengths, any
    block size, any injective block layout."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg, p = gqa_setup

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 2 ** 16),
        bs=st.sampled_from([2, 4, 8]),
        lengths=st.lists(st.integers(0, 14), min_size=1, max_size=4),
    )
    def inner(seed, bs, lengths):
        _gqa_case(cfg, p, np.random.default_rng(seed), lengths, bs, 16)

    inner()


# ------------------------------------------------------- host bookkeeping
def test_radix_match_insert_and_lru_leaf_first_eviction():
    r = RadixPrefixIndex(2)
    assert r.insert([1, 2, 3, 4], [10, 11]) == [10, 11]
    assert r.insert([5, 6], [12]) == [12]
    # duplicate chunk is not re-adopted: the canonical (tree) block
    # comes back so the caller can reclaim its copy
    assert r.insert([1, 2, 9, 9], [13, 14]) == [10, 14]
    assert r.match([1, 2, 3, 4, 7]) == [10, 11]
    assert r.match([1, 2, 9, 9]) == [10, 14]
    assert r.match([5, 6, 1]) == [12]
    assert r.match([3, 4]) == []
    # partial trailing block is never indexed or matched
    assert r.match([1, 2, 3]) == [10]

    r2 = RadixPrefixIndex(2)
    r2.insert([1, 2, 3, 4], [10, 11])  # stamp 1 (chain)
    r2.insert([5, 6], [12])  # stamp 2
    # leaf-first: 10 has a child, so the oldest LEAF (11) goes first
    assert r2.evict_lru(lambda b: True) == 11
    assert r2.evict_lru(lambda b: True) == 10
    assert r2.evict_lru(lambda b: True) == 12
    assert r2.evict_lru(lambda b: True) is None

    r3 = RadixPrefixIndex(2)
    r3.insert([1, 2, 3, 4], [10, 11])
    r3.insert([5, 6], [12])
    r3.match([1, 2, 3, 4])  # touch chain A: now newer than 12
    assert r3.evict_lru(lambda b: True) == 12
    # predicate (refcount gate) is honored: 11 is the only leaf, and
    # inner node 10 may not leapfrog it
    assert r3.evict_lru(lambda b: b != 11) is None
    r4 = RadixPrefixIndex(2)
    r4.insert([1, 2], [20])
    assert r4.evict_lru(lambda b: False) is None


def _mini_kv(n_slots=2, cache_len=16, block_size=4, **kw):
    cfg = reduce_for_smoke(get_config(GQA_ARCH))
    return cfg, PagedKVCache(
        cfg, n_slots, cache_len, block_size=block_size, **kw
    )


def test_admit_free_refcounts_and_reuse():
    cfg, kv = _mini_kv()
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    assert prefix_cacheable(cfg)
    assert kv.admit_slot(0, prompt) == 0  # cold: nothing cached
    used = [b for b in kv.tables[0] if b != kv.trash]
    assert len(used) == 3  # ceil(9 / 4) blocks cover the prompt
    assert all(kv.refcount[b] == 1 for b in used)
    kv.free_slot(0, tokens=prompt)
    assert kv.n_free == 2 and all(kv.refcount[b] == 0 for b in used)
    # full blocks stayed radix-indexed; the partial tail was recycled
    assert kv.blocks_cached == 2

    past = kv.admit_slot(0, prompt)
    assert past == 8  # both full blocks reused, last token recomputed
    assert kv.stats.hits == 1 and kv.stats.hit_tokens == 8
    # shared prefix: admit the same prompt into the other slot
    kv.admit_slot(1, prompt)
    shared = kv.tables[0][:2].copy()
    assert list(kv.tables[1][:2]) == list(shared)
    assert all(kv.refcount[b] == 2 for b in shared)
    # the uncached tail blocks are private
    assert kv.tables[0][2] != kv.tables[1][2]
    kv.free_slot(0)
    kv.free_slot(1)
    assert all(kv.refcount[b] == 0 for b in shared)


def test_match_capped_below_full_prompt():
    """A fully-cached prompt still recomputes its last token (the
    prefill logits sample the first generated token)."""
    cfg, kv = _mini_kv()
    prompt = np.arange(8, dtype=np.int32)
    kv.admit_slot(0, prompt)
    kv.free_slot(0, tokens=prompt)
    assert kv.match_tokens(prompt) == 4  # not 8: last block recomputed
    assert kv.admit_slot(1, prompt) == 4


def test_on_demand_alloc_and_exhaustion():
    cfg, kv = _mini_kv(n_slots=1, cache_len=16, block_size=4, n_blocks=4)
    prompt = np.arange(5, dtype=np.int32)
    kv.admit_slot(0, prompt)
    assert kv.blocks_in_use == 2
    kv.ensure_block(0, 7)  # still inside block 1
    assert kv.blocks_in_use == 2
    kv.ensure_block(0, 8)  # crosses into logical block 2
    assert kv.blocks_in_use == 3
    kv.free_slot(0, tokens=prompt)

    # radix-cached blocks are reclaimed LRU when the free list runs dry
    other = np.arange(100, 113, dtype=np.int32)
    kv.admit_slot(0, other)
    assert kv.stats.evictions > 0
    kv.free_slot(0)

    cfg2, tiny = _mini_kv(n_slots=1, cache_len=16, block_size=4, n_blocks=2)
    with pytest.raises(RuntimeError, match="exhausted"):
        tiny.admit_slot(0, np.arange(12, dtype=np.int32))


def test_commit_dedups_concurrent_duplicate_blocks():
    """Two slots admitted in the same wave (before either commits)
    each compute the shared prefix's blocks; the second commit must
    repoint to the first's canonical blocks and reclaim its duplicates
    IMMEDIATELY — not when the slot eventually frees."""
    cfg, kv = _mini_kv()
    prompt_a = np.arange(9, dtype=np.int32)
    prompt_b = np.concatenate([np.arange(8), [99]]).astype(np.int32)
    # both admitted cold (empty radix): each allocates its own blocks
    assert kv.admit_slot(0, prompt_a) == 0
    assert kv.admit_slot(1, prompt_b) == 0
    dup = [int(b) for b in kv.tables[1][:2]]
    assert kv.blocks_in_use == 6  # 3 + 3, no sharing yet
    kv.commit_prompt(0, prompt_a)
    before = kv.blocks_in_use
    kv.commit_prompt(1, prompt_b)
    # slot 1's two full prefix blocks were deduped against slot 0's
    canon = [int(b) for b in kv.tables[0][:2]]
    assert [int(b) for b in kv.tables[1][:2]] == canon
    assert all(kv.refcount[b] == 2 for b in canon)
    assert all(kv.refcount[b] == 0 and b in kv._free for b in dup)
    assert kv.blocks_in_use == before - 2
    assert kv.stats.dedup_blocks == 2
    kv.free_slot(0)
    kv.free_slot(1)
    assert all(kv.refcount[b] == 0 for b in canon)


def test_stats_and_reclaim_zero_traffic_edge_cases():
    """hit_rate with no lookups, match/admit of empty and one-token
    prompts, and reclaimed_bytes at zero cache_len are all well-defined
    (no division by zero, no negative reclaim, no negative prefix)."""
    cfg, kv = _mini_kv()
    assert kv.stats.hit_rate == 0.0  # 0 lookups: defined, not 0/0
    assert kv.match_tokens(np.asarray([], np.int32)) == 0
    assert kv.match_tokens(np.asarray([7], np.int32)) == 0
    # a cached block must not make a 1-token prompt match negative/positive
    prompt = np.arange(8, dtype=np.int32)
    kv.admit_slot(0, prompt)
    kv.free_slot(0, tokens=prompt)
    assert kv.match_tokens(prompt[:1]) == 0
    assert kv.match_tokens(prompt[:0]) == 0
    # empty-prompt admission: no blocks, no negative past
    past = kv.admit_slot(1, np.asarray([], np.int32))
    assert past == 0
    assert all(b == kv.trash for b in kv.tables[1])
    assert kv.stats.hit_rate >= 0.0
    kv.free_slot(1)
    # reclaim never negative, and zero at degenerate cache_len
    assert kv.reclaimed_bytes(0) == 0
    assert kv.reclaimed_bytes(-3) == 0
    assert kv.reclaimed_bytes(1) >= 0


def test_copy_on_write_preserves_shared_reader():
    cfg, kv = _mini_kv()
    prompt = np.arange(8, dtype=np.int32)
    kv.admit_slot(0, prompt)
    kv.commit_prompt(0, prompt)
    kv.admit_slot(1, prompt)  # shares the first full block
    lb = 0
    old = int(kv.tables[0][lb])
    assert old == int(kv.tables[1][lb]) and kv.refcount[old] == 2
    # paint the shared block so the copy is observable
    top = next(k for k in kv.pools if k == "stack" or k.startswith("layer"))

    def paint(leaf):
        return (
            leaf.at[:, old].set(7.0) if top == "stack" else leaf.at[old].set(7.0)
        )

    kv.pools[top] = jax.tree.map(paint, kv.pools[top])
    new = kv.copy_on_write(0, lb)
    assert new != old
    assert int(kv.tables[0][lb]) == new and int(kv.tables[1][lb]) == old
    assert kv.refcount[old] == 1 and kv.refcount[new] == 1
    leaf = jax.tree.leaves(kv.pools[top])[0]
    got = leaf[:, new] if top == "stack" else leaf[new]
    np.testing.assert_allclose(np.asarray(got, np.float32), 7.0)
    assert kv.stats.cow_copies == 1


def test_truncate_frees_tail_blocks_at_last_reference():
    """Speculative rollback: dropping the rejected tail releases whole
    blocks only when this slot held the last reference, and a kept
    partial tail stays in place when private."""
    cfg, kv = _mini_kv()
    prompt = np.arange(9, dtype=np.int32)
    kv.admit_slot(0, prompt)  # 3 blocks, length 9
    used = [int(b) for b in kv.tables[0] if b != kv.trash]
    for pos in range(9, 14):  # grow to 14 tokens = 4 blocks
        kv.ensure_block(0, pos)
    assert kv.blocks_in_use == 4
    kv.truncate(0, 9)  # tail block rc==1, unindexed: back to the pool
    assert int(kv.lengths[0]) == 9
    assert kv.blocks_in_use == 3
    assert [int(b) for b in kv.tables[0] if b != kv.trash] == used
    kv.truncate(0, 9)  # no-op truncate is safe
    assert kv.blocks_in_use == 3
    kv.truncate(0, 6)  # within-block: private partial tail kept as-is
    assert int(kv.lengths[0]) == 6
    assert kv.blocks_in_use == 2
    assert [int(b) for b in kv.tables[0] if b != kv.trash] == used[:2]
    kv.truncate(0, 0)  # full rollback keeps the slot claimed
    assert kv.blocks_in_use == 0
    assert all(b == kv.trash for b in kv.tables[0])
    kv.free_slot(0)
    assert kv.n_free == 2


def test_truncate_shared_and_radix_tails_cow_detach():
    """A kept partial tail block that other readers (or the radix
    index's immutable chunk) still see is detached by copy-on-write:
    later decode writes land at positions >= n inside it."""
    cfg, kv = _mini_kv()
    prompt = np.arange(9, dtype=np.int32)
    kv.admit_slot(0, prompt)
    kv.commit_prompt(0, prompt)
    kv.admit_slot(1, prompt)  # shares both full prompt blocks
    shared = [int(b) for b in kv.tables[0][:2]]
    assert [int(b) for b in kv.tables[1][:2]] == shared
    before = kv.stats.cow_copies
    kv.truncate(1, 6)  # partial tail inside shared block 1
    assert int(kv.lengths[1]) == 6
    assert int(kv.tables[1][0]) == shared[0]  # full block: still shared
    assert int(kv.tables[1][1]) != shared[1]  # partial tail: detached
    assert kv.refcount[shared[1]] == 1  # slot 0 keeps the original
    assert kv.stats.cow_copies == before + 1
    kv.free_slot(1)
    # sole-reference but RADIX-INDEXED tail: must also detach, and the
    # original stays radix-reclaimable at refcount 0 (not on the free
    # list — eviction owns it)
    kv.truncate(0, 7)
    assert int(kv.tables[0][1]) != shared[1]
    assert kv.refcount[shared[1]] == 0
    assert shared[1] in kv.radix
    assert shared[1] not in kv._free
    kv.free_slot(0)


def test_prefix_cacheable_gating():
    assert prefix_cacheable(reduce_for_smoke(get_config(GQA_ARCH)))
    assert prefix_cacheable(reduce_for_smoke(get_config(MLA_ARCH)))
    jamba = reduce_for_smoke(get_config("jamba-v0.1-52b"))
    assert not prefix_cacheable(jamba)  # recurrent state: no token-keyed reuse
    kv = PagedKVCache(jamba, 2, 16, block_size=4)
    assert kv.radix is None  # paged layout still works, reuse disabled


def test_tier_sizes_grow_hot_set_with_reclaimed_kv():
    """The tentpole's budget story: KV bytes reclaimed by paging feed
    straight into the HBM hot-expert budget."""
    cfg = reduce_for_smoke(get_config(GQA_ARCH))
    w_bytes = 3 * cfg.d_model * cfg.moe.d_expert * 2
    n_moe = sum(cfg.uses_moe_layer(i) for i in range(cfg.n_layers))
    base = tier_sizes(cfg, hbm_budget_frac=0.0)
    grown = tier_sizes(
        cfg, hbm_budget_frac=0.0,
        reclaimed_kv_bytes=3 * w_bytes * n_moe,
    )
    assert grown.n_hot > base.n_hot
    assert grown.n_hot + grown.n_warm + grown.n_cold == cfg.moe.n_experts


# --------------------------------------------- serving-level invariants
CACHE_LEN = 20


@pytest.fixture(scope="module")
def serve_setup():
    cfg = reduce_for_smoke(get_config(GQA_ARCH))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_prefix_hit_admission_identical_to_cold(serve_setup):
    """Flagship: serving with radix prefix reuse produces token-for-token
    the same generations as serving with reuse disabled (every
    admission cold), while actually reusing blocks."""
    cfg, params = serve_setup
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    reqs = [
        Request(
            rid=i,
            prompt=np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, 3).astype(np.int32)]
            ),
            max_new_tokens=4,
        )
        for i in range(4)
    ]

    warm = ServingLoop(cfg, params, batch_size=2, n_groups=1,
                       cache_len=CACHE_LEN)
    for r in reqs:
        warm.submit(copy.deepcopy(r))
    done = warm.run(max_steps=400)
    assert len(done) == len(reqs)
    assert warm.kv.stats.hit_tokens > 0, "shared prefix never hit the cache"
    warm_out = {r.rid: r.generated for r in done}

    cold = ServingLoop(cfg, params, batch_size=2, n_groups=1,
                       cache_len=CACHE_LEN, prefix_cache=False)
    for r in reqs:
        cold.submit(copy.deepcopy(r))
    done = cold.run(max_steps=400)
    assert cold.kv.stats.hit_tokens == 0
    for r in done:
        assert r.generated == warm_out[r.rid], (
            f"rid={r.rid}: warm {warm_out[r.rid]} != cold {r.generated}"
        )
    # eviction left the pool consistent: every slot drained
    assert warm.kv.n_free == 2 and warm.kv.blocks_in_use == 0


def test_dead_row_in_group_step_cannot_corrupt_blocks(serve_setup):
    """Regression: a request that completes during admission (1 new
    token) sits dead in the same iteration's group step while its block
    table is still populated. The dead row's garbage K/V write must go
    to the trash block — not block 0 of the finished slot, which is
    later radix-indexed (or already shared)."""
    cfg, params = serve_setup
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    reqs = [
        Request(
            rid=rid,
            prompt=np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, 3).astype(np.int32)]
            ),
            max_new_tokens=n_new,
        )
        for rid, n_new in ((0, 1), (1, 4), (2, 4))
    ]

    def serve(**kw):
        loop = ServingLoop(cfg, params, batch_size=2, n_groups=1,
                           cache_len=CACHE_LEN, **kw)
        # rid0 done at admission -> dead row during rid1's decode steps
        loop.submit(copy.deepcopy(reqs[0]))
        loop.submit(copy.deepcopy(reqs[1]))
        loop.run(max_steps=200)
        # rid2 prefix-hits rid0/rid1's committed blocks (warm loop)
        loop.submit(copy.deepcopy(reqs[2]))
        loop.run(max_steps=200)
        return loop

    warm = serve()
    assert warm.kv.stats.hit_tokens > 0
    warm_out = {r.rid: r.generated for r in warm.completions}
    cold = serve(prefix_cache=False)
    for r in cold.completions:
        assert r.generated == warm_out[r.rid], (
            f"rid={r.rid}: warm {warm_out[r.rid]} != cold {r.generated}"
        )


def test_last_sampled_token_block_never_indexed(serve_setup):
    """Regression: the final generated token is sampled but never fed
    back through decode, so its K/V does not exist — a block completed
    by it must not enter the radix (prompt + generated[:-1] only)."""
    cfg, params = serve_setup
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    loop = ServingLoop(cfg, params, batch_size=1, n_groups=1, cache_len=12)
    loop.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
    loop.run(max_steps=100)
    (done,) = loop.completions
    full = np.concatenate([prompt, np.asarray(done.generated, np.int32)])
    assert len(full) == 8  # 2 full blocks of 4 — but the last token's
    # K/V was never computed, so only the first block may be cached
    probe = np.concatenate([full, full[:1]])  # lift the plen-1 cap
    assert loop.kv.match_tokens(probe) == 4


def test_paged_loop_serves_recurrent_arch(serve_setup):
    """Hybrid (Mamba-mixer) archs run on the paged layout too — prefix
    reuse is simply gated off."""
    cfg = reduce_for_smoke(get_config("jamba-v0.1-52b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=16)
    assert loop.kv.radix is None
    for i in range(3):
        loop.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, 5 + i).astype(np.int32),
            max_new_tokens=3,
        ))
    done = loop.run(max_steps=300)
    assert len(done) == 3
    assert all(len(r.generated) == 3 for r in done)


def test_recurrent_arch_serving_matches_contiguous_reference():
    """Jamba (mamba and attention layers) served through ServingLoop in
    fp32 emits the tokens of the contiguous `prefill` + `decode_step`
    reference. Each prompt prefills at its exact width from a zero
    recurrent state (the paged forward handed no states), the 1-token
    prompt as a chunk of one; decode then steps the rows' states."""
    cfg = dataclasses.replace(
        reduce_for_smoke(get_config("jamba-v0.1-52b")),
        param_dtype="float32", compute_dtype="float32",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(31)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (1, 5, 7)]
    new, cache_len = 4, 16
    loop = ServingLoop(cfg, params, batch_size=4, n_groups=1,
                       cache_len=cache_len, bucket_table=None)
    widths, prefill_fn = [], loop.engine.prefill_slots_paged

    def record(suffixes, *args):
        widths.append(np.shape(suffixes)[1])
        return prefill_fn(suffixes, *args)

    loop.engine.prefill_slots_paged = record
    for rid, prompt in enumerate(prompts):
        loop.submit(Request(rid=rid, prompt=prompt, max_new_tokens=new))
    served = {r.rid: r.generated for r in loop.run(max_steps=100)}
    assert sorted(widths) == [1, 5, 7]
    for rid, prompt in enumerate(prompts):
        n = len(prompt)
        logits, cache = prefill(
            params, cfg, {"tokens": jnp.asarray(prompt[None])},
            cache_len=cache_len, token_mask=jnp.ones((1, n), bool),
        )
        want = [int(jnp.argmax(logits[0]))]
        for t in range(new - 1):
            logits, cache, _ = decode_step(
                params, cfg, jnp.asarray([[want[-1]]], jnp.int32), cache, n + t
            )
            want.append(int(jnp.argmax(logits[0])))
        assert served[rid] == want, f"prompt of {n}: {served[rid]} != {want}"
