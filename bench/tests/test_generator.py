"""The traffic generator: one seed, one schedule; every seed the same
set of sizes and gaps in another order."""
import numpy as np

import generator

BENCH_DIR = generator.__file__.rsplit("/", 1)[0]


def _sched(mix, seed, slots=0):
    return generator.generate(mix, 1000, seed, horizon_s=40, slots=slots)


def test_same_seed_same_schedule_large_seed():
    mix = generator.load_mix(BENCH_DIR, "chat-shared")
    a, b = _sched(mix, 2**33 + 1), _sched(mix, 2**33 + 1)
    assert np.array_equal(a.due_s, b.due_s)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))


def test_seeds_share_sizes_and_gaps_in_another_order():
    for name in ("chat-shared", "decode-long"):
        mix = generator.load_mix(BENCH_DIR, name)
        a, b = _sched(mix, 1), _sched(mix, 2)
        blk = mix["order_block"]
        la = np.array([len(p) for p in a.prompts])
        lb = np.array([len(p) for p in b.prompts])
        assert np.array_equal(la, lb) == (blk == 1)
        for s in range(0, len(la) - blk, blk):
            assert sorted(la[s:s + blk]) == sorted(lb[s:s + blk])
            assert sorted(a.max_new[s:s + blk]) == sorted(b.max_new[s:s + blk])
        assert not np.array_equal(a.prompts[0][:8], b.prompts[0][:8])


def test_bounds_and_cache_len():
    mix = generator.load_mix(BENCH_DIR, "chat-shared")
    s = _sched(mix, 5)
    lens = np.array([len(p) for p in s.prompts])
    assert lens.min() >= 512 + 16 and lens.max() <= 512 + 512
    assert s.max_new.min() >= 16 and s.max_new.max() <= 256
    assert generator.cache_len(mix) == 512 + 512 + 256
    assert np.all(np.diff(s.due_s) >= 0) and not s.closed
    # four system prompts: every prompt starts with one of them
    heads = {tuple(p[:512]) for p in s.prompts}
    assert len(heads) <= 4
    mix = generator.load_mix(BENCH_DIR, "decode-long")
    s = _sched(mix, 5)
    assert s.closed and np.all(s.due_s == 0)
    assert generator.cache_len(mix) == 128 + 1024


def test_steady_backlog_starts_residents_part_way():
    mix = generator.load_mix(BENCH_DIR, "decode-long")
    a, b = _sched(mix, 2**33 + 7, slots=16), _sched(mix, 3, slots=16)
    assert a.resident == 16 and len(a.prompts) == 16 + mix["arrival"]["requests"]
    # the same sizes for every seed, other tokens
    assert [len(p) for p in a.prompts] == [len(p) for p in b.prompts]
    assert np.array_equal(a.max_new, b.max_new)
    history = a.warm_prompts[-1]
    lo, hi = generator.length_bounds(mix["prompt"]["length"])
    for p, left in zip(a.prompts[:16], a.max_new[:16]):
        assert left >= 1 and len(p) + left <= generator.cache_len(mix)
        # what runs past the longest prompt is the shared history
        assert np.array_equal(p[: max(0, len(p) - hi)], history[: max(0, len(p) - hi)])
    assert max(len(p) for p in a.prompts[:16]) > hi
    # a slot spends longer in a long request: the residents' whole
    # lengths (age + to go) run longer than the backlog's
    total = np.array([len(p) for p in a.prompts[:16]]) + a.max_new[:16]
    assert np.median(total) > np.median(a.max_new[16:])
    assert max(len(p) for p in a.prompts[16:]) <= hi
    assert _sched(mix, 3).resident == 0  # no slots given: no residents
