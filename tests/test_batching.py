"""Unit tests: ZigzagBatcher composition logic (FIFO and bucket-aware
admission with the starvation cap), the BucketTable policy, and the
per-slot cache row helpers (gather/scatter/reset + byte accounting)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.model import init_cache
from repro.serving.batching import BucketTable, Request, ZigzagBatcher
from repro.serving.kv_cache import (
    cache_bytes,
    gather_slots,
    reset_slots,
    scatter_slots,
)


def _req(rid, plen=4, new=3):
    return Request(rid=rid, prompt=np.arange(plen, dtype=np.int32),
                   max_new_tokens=new)


# --------------------------------------------------------- ZigzagBatcher
def test_admit_fills_and_reports_slots():
    b = ZigzagBatcher(4, n_groups=2)
    for i in range(6):
        b.submit(_req(i))
    freed, filled = b.admit()
    assert freed == [] and filled == [0, 1, 2, 3]
    assert len(b.queue) == 2
    assert all(b.slots[i].pos == 4 for i in filled)  # pos = prompt_len


def test_slot_recycling_after_done():
    b = ZigzagBatcher(2, n_groups=1)
    for i in range(4):
        b.submit(_req(i, new=2))
    b.admit()
    # finish request 0 only
    b.slots[0].request.generated = [7, 8]
    freed, filled = b.admit()
    assert freed == [0] and filled == [0]  # recycled and refilled
    assert b.completed[0].rid == 0
    assert b.slots[0].request.rid == 2  # FIFO admission
    assert b.slots[1].request.rid == 1  # untouched


def test_group_rotation_over_idle_groups():
    b = ZigzagBatcher(4, n_groups=2)
    # only group 1's slots (2, 3) hold work
    for i in range(2):
        b.submit(_req(i, new=4))
    b.admit()
    b.slots[2].request = b.slots[0].request
    b.slots[3].request = b.slots[1].request
    b.slots[0].request = b.slots[1].request = None
    seen = []
    for _ in range(4):
        gb = b.next_group()
        seen.append(None if gb is None else gb[0])
    # rotation alternates; group 0 is idle (None), group 1 always live
    assert seen == [None, 1, None, 1]


def test_next_group_masks_dead_slots_fixed_width():
    b = ZigzagBatcher(4, n_groups=2)
    b.submit(_req(0, plen=5, new=4))
    b.admit()  # only slot 0 occupied
    g, idxs, toks, pos, live = b.next_group()
    assert g == 0 and idxs == [0, 1]
    assert toks.shape == (2, 1) and pos.shape == (2,)
    assert live.tolist() == [True, False]
    assert toks[0, 0] == 4  # last prompt token (no generated yet)
    assert pos[0] == 5 and toks[1, 0] == 0 and pos[1] == 0


def test_record_advances_positions_and_utilization():
    b = ZigzagBatcher(2, n_groups=1)
    b.submit(_req(0, new=2))
    b.admit()
    assert b.utilization == 0.5
    _, idxs, toks, pos, live = b.next_group()
    b.record([0], np.asarray([9]))
    assert b.slots[0].request.generated == [9]
    assert b.slots[0].pos == 5
    b.record([0], np.asarray([3]))
    assert b.slots[0].request.done
    assert b.utilization == 0.0  # done requests don't count as live


def test_next_batch_legacy_path_still_recycles():
    b = ZigzagBatcher(2, n_groups=1)
    for i in range(3):
        b.submit(_req(i, new=1))
    out = b.next_batch()
    assert out is not None
    live, toks = out
    assert live == [0, 1] and toks.shape == (2, 1)
    b.record(live, np.asarray([5, 6]))  # both done (new=1)
    b.next_batch()  # recycles + admits rid=2
    assert {r.rid for r in b.completed} == {0, 1}
    assert b.slots[0].request.rid == 2


# --------------------------------------------------------- bucket policy
def test_bucket_table_powers_of_two():
    assert BucketTable.powers_of_two(8).widths == (8,)
    assert BucketTable.powers_of_two(16).widths == (8, 16)
    assert BucketTable.powers_of_two(24).widths == (8, 16, 24)
    assert BucketTable.powers_of_two(40, min_width=4).widths == (4, 8, 16, 32, 40)
    t = BucketTable.powers_of_two(24)
    assert t.bucket_of(1) == 8 and t.bucket_of(8) == 8
    assert t.bucket_of(9) == 16 and t.bucket_of(17) == 24
    with pytest.raises(ValueError):
        t.bucket_of(25)
    with pytest.raises(AssertionError):
        BucketTable((16, 8))  # not ascending


def test_bucket_admission_groups_same_bucket():
    table = BucketTable((8, 16))
    b = ZigzagBatcher(4, n_groups=2, bucket_table=table, max_admit_wait=2)
    for i, plen in enumerate([5, 12, 7, 3]):  # buckets 8, 16, 8, 8
        b.submit(_req(i, plen=plen))
    # head (bucket 8) anchors a partial cohort (3 of 4 free slots, and a
    # bucket-16 request is also queued): held for same-bucket arrivals
    freed, filled = b.admit()
    assert freed == [] and filled == []
    # cap reached: cohort rids 0, 2, 3 admitted together (FIFO within the
    # bucket); the now-homogeneous remainder (rid 1) follows in-call
    _, filled = b.admit()
    assert [b.slots[i].request.rid for i in filled] == [0, 2, 3, 1]
    assert b.queue == []


def test_bucket_admission_homogeneous_queue_never_waits():
    """When every queued request shares one bucket there is nothing to
    wait for: admit immediately even as a partial cohort."""
    table = BucketTable((8, 16))
    b = ZigzagBatcher(4, n_groups=2, bucket_table=table, max_admit_wait=100)
    b.submit(_req(0, plen=5))
    b.submit(_req(1, plen=7))
    _, filled = b.admit()
    assert [b.slots[i].request.rid for i in filled] == [0, 1]


def test_bucket_admission_starvation_cap():
    """A lone long prompt behind nothing of its bucket is held back at
    most max_admit_wait admit calls, then admitted as a partial cohort."""
    table = BucketTable((8, 16))
    b = ZigzagBatcher(4, n_groups=2, bucket_table=table, max_admit_wait=3)
    b.submit(_req(0, plen=12))  # bucket 16
    b.submit(_req(1, plen=5))  # bucket 8 behind it
    for call in range(2):  # partial cohort held (other buckets queued)
        _, filled = b.admit()
        assert filled == [], f"admitted too early on call {call}"
    _, filled = b.admit()  # 3rd call: wait == max_admit_wait -> admit
    assert [b.slots[i].request.rid for i in filled] == [0, 1]
    assert b.queue == []


def test_bucket_admission_fills_free_slots_immediately():
    """A cohort that fills every free slot never waits."""
    table = BucketTable((8,))
    b = ZigzagBatcher(2, n_groups=1, bucket_table=table, max_admit_wait=100)
    for i in range(3):
        b.submit(_req(i, plen=4))
    _, filled = b.admit()
    assert len(filled) == 2 and len(b.queue) == 1


# ------------------------------------------------------------- kv cache
@pytest.fixture(scope="module")
def smoke_cfg():
    return reduce_for_smoke(get_config("granite-moe-1b-a400m"))


def test_cache_bytes_matches_hand_count(smoke_cfg):
    cfg = smoke_cfg
    b, s = 2, 8
    # pure-attention stack: each of n_layers layers caches K and V of
    # [b, s, n_kv_heads, head_dim] in bf16 (2 bytes); MoE adds no cache.
    per_layer = 2 * b * s * cfg.n_kv_heads * cfg.resolved_head_dim * 2
    assert cache_bytes(cfg, b, s) == cfg.n_layers * per_layer


def test_reset_slots_zeroes_exactly_the_recycled_rows(smoke_cfg):
    cache = init_cache(smoke_cfg, 4, 8)
    ones = jax.tree.map(jnp.ones_like, cache)
    out = reset_slots(ones, [1, 3])
    for key, sub in out.items():
        ax = 1 if key == "stack" else 0
        for leaf in jax.tree.leaves(sub):
            rows = jnp.moveaxis(leaf, ax, 0)
            assert not np.any(np.asarray(rows[1])) and not np.any(np.asarray(rows[3]))
            assert np.all(np.asarray(rows[0]) == 1) and np.all(np.asarray(rows[2]) == 1)


def test_gather_scatter_roundtrip(smoke_cfg):
    cache = init_cache(smoke_cfg, 4, 8)
    # make rows distinguishable: row i = i + 1 everywhere
    def rowstamp(a, ax):
        shape = [1] * a.ndim
        shape[ax] = a.shape[ax]
        return jnp.broadcast_to(
            (jnp.arange(a.shape[ax], dtype=a.dtype) + 1).reshape(shape), a.shape
        )
    stamped = {
        k: jax.tree.map(lambda a, ax=(1 if k == "stack" else 0): rowstamp(a, ax), v)
        for k, v in cache.items()
    }
    sub = gather_slots(stamped, [2, 0])
    for key, s in sub.items():
        ax = 1 if key == "stack" else 0
        for leaf in jax.tree.leaves(s):
            rows = np.asarray(jnp.moveaxis(leaf, ax, 0))
            assert np.all(rows[0] == 3) and np.all(rows[1] == 1)
    # scatter the gathered rows into a zero cache and read them back
    zero = jax.tree.map(jnp.zeros_like, stamped)
    back = scatter_slots(zero, sub, [2, 0])
    for key, s in back.items():
        ax = 1 if key == "stack" else 0
        for leaf in jax.tree.leaves(s):
            rows = np.asarray(jnp.moveaxis(leaf, ax, 0))
            assert np.all(rows[2] == 3) and np.all(rows[0] == 1)
            assert not rows[1].any() and not rows[3].any()
