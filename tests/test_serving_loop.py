"""End-to-end continuous-batching serving loop tests.

The flagship invariant: batched zigzag serving is token-for-token
identical to single-request generation (engine default
cold_capacity_frac=1.0 keeps the tiered dispatch dropless, decode rows
are computed independently, and migrations are exact weight swaps).
"""
import copy

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.model import init_params
from repro.serving.batching import Request
from repro.serving.loop import ServingLoop

CACHE_LEN = 16


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _staggered_requests(cfg, n=8, new_tokens=6):
    rng = np.random.default_rng(7)
    reqs = []
    for rid in range(n):
        plen = 5 + rid % 4  # prompt lengths 5..8, staggered
        reqs.append(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=new_tokens,
        ))
    return reqs


def test_batched_loop_matches_single_request_generation(setup):
    cfg, params = setup
    reqs = _staggered_requests(cfg, n=8)

    loop = ServingLoop(cfg, params, batch_size=8, n_groups=2,
                       cache_len=CACHE_LEN)
    for r in reqs:
        loop.submit(copy.deepcopy(r))
    done = loop.run(max_steps=500)
    assert len(done) == 8
    batched = {r.rid: r.generated for r in done}
    assert all(len(toks) == 6 for toks in batched.values())

    # one width-1 loop reused across requests: migrations/predictor state
    # carry over but are output-invariant (exact swaps, dropless dispatch)
    solo = ServingLoop(cfg, params, batch_size=1, n_groups=1,
                       cache_len=CACHE_LEN)
    for r in reqs:
        solo.submit(copy.deepcopy(r))
        solo.run(max_steps=200)
    for r in solo.completions:
        assert r.generated == batched[r.rid], (
            f"rid={r.rid}: batched {batched[r.rid]} != solo {r.generated}"
        )


def test_loop_oversubscribed_queue_drains(setup):
    """More requests than slots: continuous refill must complete all."""
    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=4, n_groups=2,
                       cache_len=CACHE_LEN)
    reqs = _staggered_requests(cfg, n=10, new_tokens=4)
    for r in reqs:
        loop.submit(r)
    done = loop.run(max_steps=500)
    assert len(done) == 10
    assert sorted(r.rid for r in done) == list(range(10))
    assert all(len(r.generated) == 4 for r in done)
    st = loop.stats
    assert st.admitted == 10 and st.completed == 10
    assert st.generated_tokens == 10 * 4
    assert len(st.latencies_s) == 10
    assert 0.0 < st.mean_utilization <= 1.0
    assert st.tokens_per_s > 0
    # slot eviction recycled every row back to the free pool
    assert loop.kv.n_free == 4
    assert loop.engine.stats.prefills == 10


def test_paged_steps_donate_the_kv_pools(setup):
    """Decode and prefill write K/V into the pool buffers they are
    handed: every pool array passed in is deleted, so one pool is live
    however many steps are in flight, and `kv.pools` holds the live one."""
    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1,
                       cache_len=CACHE_LEN)
    eng, seen = loop.engine, []
    for name, at in (("_step_paged", 2), ("_prefill_paged", 5)):
        def record(*args, _fn=getattr(eng, name), _name=name, _at=at):
            seen.append((_name, args[_at]))
            return _fn(*args)

        setattr(eng, name, record)
    for r in _staggered_requests(cfg, n=3, new_tokens=3):
        loop.submit(r)
    assert len(loop.run(max_steps=200)) == 3
    assert {name for name, _ in seen} == {"_step_paged", "_prefill_paged"}
    for name, pools in seen:
        assert all(a.is_deleted() for a in jax.tree.leaves(pools)), name
    assert not any(a.is_deleted() for a in jax.tree.leaves(loop.kv.pools))


def test_mixed_lengths_bucketed_compiles_and_matches_solo(setup):
    """A mixed-length trace (>=6 distinct prompt lengths) stays within
    len(bucket_table) x n_width_buckets(blocks_per_slot) distinct
    prefill compiles (chunk-width buckets x pow2 past-table widths) AND
    remains token-for-token identical to single-request generation
    (acceptance criteria for bucketed + chunked paged prefill)."""
    cfg, params = setup
    lengths = [3, 5, 7, 9, 12, 17]  # 6 distinct lengths, 3 buckets
    new_tokens = 4
    cache_len = max(lengths) + new_tokens
    rng = np.random.default_rng(3)
    reqs = [
        Request(rid=rid,
                prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                max_new_tokens=new_tokens)
        for rid, plen in enumerate(lengths)
    ]

    loop = ServingLoop(cfg, params, batch_size=4, n_groups=2,
                       cache_len=cache_len)
    for r in reqs:
        loop.submit(copy.deepcopy(r))
    done = loop.run(max_steps=500)
    assert len(done) == len(lengths)
    from repro.kernels.paged_attention import n_width_buckets

    bound = len(loop.bucket_table) * n_width_buckets(loop.kv.blocks_per_slot)
    assert loop.engine.prefill_compiles <= bound
    batched = {r.rid: r.generated for r in done}

    solo = ServingLoop(cfg, params, batch_size=1, n_groups=1,
                       cache_len=cache_len)
    for r in reqs:
        solo.submit(copy.deepcopy(r))
        solo.run(max_steps=200)
    for r in solo.completions:
        assert r.generated == batched[r.rid], (
            f"rid={r.rid}: batched {batched[r.rid]} != solo {r.generated}"
        )


def test_loop_overlapped_replan_migrates(setup):
    """Zigzag groups: migrations still happen (deferred replan path)."""
    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=4, n_groups=2,
                       cache_len=CACHE_LEN)
    for r in _staggered_requests(cfg, n=4, new_tokens=6):
        loop.submit(r)
    loop.run(max_steps=500)
    assert loop.engine.stats.plans > 0
    # every decode group step contributed its loads to exactly one replan
    assert loop.stats.decode_steps == loop.engine.stats.steps
