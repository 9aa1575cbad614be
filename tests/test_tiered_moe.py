import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.moe import init_moe, moe_forward
from repro.serving.tiered_moe import (
    TierSizes,
    apply_migrations,
    init_tiered_state,
    tier_sizes,
    tiered_moe_forward,
)


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("deepseek-v2-236b"))
    rng = jax.random.PRNGKey(0)
    p = init_moe(rng, cfg)
    sizes = TierSizes(2, 3, 3)
    state = init_tiered_state(rng, cfg, sizes)
    wstack = jnp.stack(
        [p["w_gate"], p["w_up"], p["w_down"].transpose(0, 2, 1)], axis=1
    )
    state["hot"] = wstack[:2]
    state["warm"] = wstack[2:5]
    state["cold"] = wstack[5:8]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, cfg.d_model), jnp.bfloat16)
    return cfg, p, state, x


def test_tiered_equals_flat_moe(setup):
    cfg, p, state, x = setup
    y_t, counts_t = tiered_moe_forward(p, state, cfg, x, cold_capacity_frac=1.0)
    out = moe_forward(p, cfg, x, full_capacity=True)
    np.testing.assert_allclose(
        np.asarray(y_t, np.float32), np.asarray(out.y, np.float32), atol=1e-2
    )
    np.testing.assert_array_equal(np.asarray(counts_t), np.asarray(out.expert_counts))


@pytest.mark.parametrize("donated", [False, True], ids=["functional", "in_place"])
def test_migration_preserves_outputs(setup, donated):
    cfg, p, state, x = setup
    ref, _ = tiered_moe_forward(p, state, cfg, x, cold_capacity_frac=1.0)
    # chain of swaps across all three tiers
    plan = jnp.asarray(
        [[0, 0, 0, 2, 1], [3, 1, 1, 0, 0], [-1, 0, 0, 0, 0], [5, 2, 0, 1, 2]],
        jnp.int32,
    )
    if donated:  # the engine's jit: the state's buffers are reused
        st2 = jax.jit(apply_migrations, donate_argnums=0)(
            jax.tree.map(jnp.copy, state), plan)
    else:
        st2 = apply_migrations(state, plan)
    got, _ = tiered_moe_forward(p, st2, cfg, x, cold_capacity_frac=1.0)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), atol=1e-2
    )
    # tables stay a permutation: every expert maps to a unique (tier, slot)
    keys = {(int(t), int(s)) for t, s in
            zip(st2["expert_tier"], st2["expert_slot"])}
    assert len(keys) == cfg.moe.n_experts


def test_tier_sizes_fit_hbm_budget():
    cfg = get_config("deepseek-v2-236b")
    s = tier_sizes(cfg)
    assert s.n_hot + s.n_warm + s.n_cold == cfg.moe.n_experts
    w_bytes = 3 * cfg.d_model * cfg.moe.d_expert * 2
    n_moe = sum(cfg.uses_moe_layer(i) for i in range(cfg.n_layers))
    from repro.hardware import TPU_V5E
    budget = 0.15 * TPU_V5E.hbm_bytes
    # at least one replicated hot expert per layer, otherwise within budget
    assert s.n_hot == max(1, int(budget / (w_bytes * n_moe)))
    assert 1 <= s.n_warm <= cfg.moe.n_experts


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v4"])
def test_tier_sizes_take_hbm_from_the_tpu_kind(monkeypatch, kind):
    """On a TPU the hot budget comes from the device_kind table, and a
    kind the table does not know is an error, not a v5e default."""
    from repro.hardware import TPU_V5E

    class FakeTpu:
        platform = "tpu"
        device_kind = kind

    cfg = get_config("granite-moe-1b-a400m")
    on_cpu = tier_sizes(cfg, n_chips=1)
    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    if kind != TPU_V5E.device_kind:
        with pytest.raises(ValueError, match="TPU v4"):
            tier_sizes(cfg, n_chips=1)
    else:
        assert tier_sizes(cfg, n_chips=1) == on_cpu


def test_engine_online_loop_runs():
    from repro.models.model import init_params
    from repro.serving.engine import (
        TriMoEServingEngine,
        fill_tiers_from_params,
        init_tiered_for_model,
    )
    from repro.serving.paged_kv import PagedKVCache

    cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    sizes = TierSizes(2, 3, 3)
    tiered = init_tiered_for_model(jax.random.PRNGKey(1), cfg, sizes)
    tiered = fill_tiers_from_params(params, tiered, cfg)
    b, s, new = 2, 8, 6
    prompts = np.asarray(jax.random.randint(rng, (b, s), 0, cfg.vocab_size),
                         np.int32)
    kv = PagedKVCache(cfg, b, s + new, prefix_cache=False)
    eng = TriMoEServingEngine(cfg, params, kv, tiered, sizes=sizes)
    slots = list(range(b))
    for i in slots:
        kv.admit_slot(i, prompts[i])
    logits = eng.prefill_slots_paged(prompts, slots, [s] * b, [0] * b)
    for i in range(new):
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
        tok = np.asarray(jnp.argmax(logits, -1), np.int32)[:, None]
        pos = np.full(b, s + i)
        for j in slots:
            kv.ensure_block(j, s + i)
        logits, counts = eng.step_slots_paged(tok, pos, slots,
                                              kv.table_rows(slots))
        eng.replan(counts, eng.last_dispatch)
    assert eng.stats.steps == new
    assert eng.stats.prefills == b


def test_token_mask_excludes_dead_tokens_from_counts(setup):
    """Dead (padded) slots in a fixed-width zigzag group must not leak
    phantom loads into the expert counts the predictor consumes."""
    cfg, p, state, x = setup
    mask = jnp.asarray([[True] * 4, [False] * 4])  # row 1 entirely dead
    y, counts = tiered_moe_forward(
        p, state, cfg, x, cold_capacity_frac=1.0, token_mask=mask
    )
    y_live, counts_live = tiered_moe_forward(
        p, state, cfg, x[:1], cold_capacity_frac=1.0
    )
    # counts: exactly the live rows' routing, nothing from dead tokens
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_live))
    assert int(counts.sum()) == 4 * cfg.moe.top_k
    # live rows' outputs are untouched by masking the dead row
    np.testing.assert_allclose(
        np.asarray(y[:1], np.float32), np.asarray(y_live, np.float32), atol=1e-2
    )


# -------------------------------------------------- in-place migrations
# granite-moe at smoke scale: one MoE slot scanned over 4 groups, 8
# experts in tiers 2/3/3 (cold padded to 16 slots). Initial layout per
# group: experts 0-1 hot 0-1, 2-4 warm 0-2, 5-7 cold 0-2. Each plan is
# a list of (expert, tier, slot, tier, slot) swaps valid in sequence.
MIGRATION_PLANS = {
    "no_op": [(-1, 0, 0, 0, 0)],
    "cross_tier": [(0, 0, 0, 2, 1)],
    "same_tier": [(2, 1, 0, 1, 2)],
    "no_op_between": [(3, 1, 1, 0, 0), (-1, 0, 0, 0, 0), (5, 2, 0, 1, 2)],
    # expert 0 hot -> warm -> cold, then back to hot
    "chained": [(0, 0, 0, 1, 1), (0, 1, 1, 2, 0), (0, 2, 0, 0, 0)],
}


@pytest.fixture(scope="module")
def stacked_parts():
    from repro.models.model import init_params
    from repro.serving.engine import fill_tiers_from_params, init_tiered_for_model

    cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    sizes = TierSizes(2, 3, 3)
    tiered = fill_tiers_from_params(
        params, init_tiered_for_model(jax.random.PRNGKey(1), cfg, sizes), cfg)
    return cfg, params, tiered, sizes


def _stacked_engine(parts):
    """A fresh engine over its own copy of the tier state (the engine
    donates what it is given)."""
    from repro.serving.engine import TriMoEServingEngine
    from repro.serving.paged_kv import PagedKVCache

    cfg, params, tiered, sizes = parts
    kv = PagedKVCache(cfg, 2, 16, prefix_cache=False)
    return TriMoEServingEngine(cfg, params, kv, jax.tree.map(jnp.copy, tiered),
                               sizes=sizes)


def _numpy_swaps(stack: dict, plan: np.ndarray, g: int) -> dict:
    """Group g's tier buffers after the weight swaps of `plan`."""
    out = {k: np.array(stack[k]) for k in ("hot", "warm", "cold")}
    bufs = [out["hot"], out["warm"], out["cold"]]
    for ea, ta, sa, tb, sb in plan:
        if ea >= 0:
            wa, wb = bufs[ta][g, sa].copy(), bufs[tb][g, sb].copy()
            bufs[ta][g, sa], bufs[tb][g, sb] = wb, wa
    return out


@pytest.mark.parametrize("g", [0, 1, 3])
@pytest.mark.parametrize("case", list(MIGRATION_PLANS))
def test_stacked_migration_swaps_only_its_group_in_place(stacked_parts, case, g):
    """`apply_planned` on a stacked layer (`engine._migrate_stack`):
    group g's buffers equal a NumPy swap of the same stack, every other
    group is bit-identical, the tables equal `_swapped`, and the tier
    arrays handed in were donated."""
    from repro.serving.engine import _swapped

    eng = _stacked_engine(stacked_parts)
    key = ("stack", "slot0", g)
    plan = np.full((eng.policy.plan_rows, 5), -1, np.int32)
    plan[: len(MIGRATION_PLANS[case])] = MIGRATION_PLANS[case]
    before = eng.tiered["stack"]["slot0"]
    host = jax.tree.map(np.array, before)
    tables = _swapped(eng._applied, [(eng._layer_index[key], plan)])
    eng.apply_planned([(key, plan)])
    after = jax.tree.map(np.asarray, eng.tiered["stack"]["slot0"])
    want = _numpy_swaps(host, plan, g)
    others = [h for h in range(host["hot"].shape[0]) if h != g]
    for k in ("hot", "warm", "cold"):
        np.testing.assert_array_equal(after[k], want[k], err_msg=k)
        np.testing.assert_array_equal(after[k][others], host[k][others])
    for k in ("expert_tier", "expert_slot"):
        np.testing.assert_array_equal(after[k][others], host[k][others])
    li = eng._layer_index[key]
    np.testing.assert_array_equal(after["expert_tier"][g], tables[0][li])
    np.testing.assert_array_equal(after["expert_slot"][g], tables[1][li])
    np.testing.assert_array_equal(eng._applied[0], tables[0])
    np.testing.assert_array_equal(eng._applied[1], tables[1])
    assert all(a.is_deleted() for a in jax.tree.leaves(before))


@pytest.mark.parametrize("kind", ["stack", "layer"])
def test_migrations_donate_the_tier_buffers(stacked_parts, kind):
    """Both migration programs alias their tier state input to output,
    so a swap never writes a fresh copy of the buffers: the arrays
    passed in are deleted, and a plan of no-ops keeps every value."""
    eng = _stacked_engine(stacked_parts)
    stack = eng.tiered["stack"]["slot0"]
    noop = jnp.full((eng.policy.plan_rows, 5), -1, jnp.int32)
    if kind == "stack":
        state, migrate, args = stack, eng._migrate_stack, (noop, 2)
    else:  # an unrolled layer's state: one group of the stack
        state = jax.tree.map(lambda a: a[2], stack)
        migrate, args = eng._migrate, (noop,)
    host = jax.tree.map(np.array, state)
    out = migrate(state, *args)
    assert all(a.is_deleted() for a in jax.tree.leaves(state))
    for k in host:
        np.testing.assert_array_equal(np.asarray(out[k]), host[k], err_msg=k)
