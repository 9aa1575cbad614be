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


def test_migration_preserves_outputs(setup):
    cfg, p, state, x = setup
    ref, _ = tiered_moe_forward(p, state, cfg, x, cold_capacity_frac=1.0)
    # chain of swaps across all three tiers
    plan = jnp.asarray(
        [[0, 0, 0, 2, 1], [3, 1, 1, 0, 0], [-1, 0, 0, 0, 0], [5, 2, 0, 1, 2]],
        jnp.int32,
    )
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
    from repro.models.model import init_params, prefill
    from repro.serving.engine import (
        TriMoEServingEngine,
        fill_tiers_from_params,
        init_tiered_for_model,
    )

    cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    sizes = TierSizes(2, 3, 3)
    tiered = init_tiered_for_model(jax.random.PRNGKey(1), cfg, sizes)
    tiered = fill_tiers_from_params(params, tiered, cfg)
    b, s, new = 2, 8, 6
    batch = {"tokens": jax.random.randint(rng, (b, s), 0, cfg.vocab_size)}
    _, cache = prefill(params, cfg, batch, cache_len=s + new)
    eng = TriMoEServingEngine(cfg, params, cache, tiered, sizes=sizes)
    tok = batch["tokens"][:, -1:]
    for i in range(new):
        logits = eng.step(tok, s + i)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    assert eng.stats.steps == new


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
