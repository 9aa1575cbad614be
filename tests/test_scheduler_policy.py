"""SchedulerPolicy API surface + online tier-scheduling behavior.

Covers the PR-7 acceptance criteria on the policy side: one resolution
rule (`resolve_policy` precedence), the deprecated bare kwargs warning
exactly once, policy validation, fixed-vs-dynamic plan sizing, freeze
semantics, and the hysteresis regression — oscillating loads inside the
hysteresis band must produce ZERO thrash events, while band-crossing
oscillation without hysteresis is counted as thrash.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.core.policy import SchedulerPolicy, resolve_policy
from repro.core.tiers import TierThresholds
from repro.models.model import init_params
from repro.serving.loop import ServingLoop

CACHE_LEN = 16


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _loop(cfg, params, **kw):
    return ServingLoop(cfg, params, batch_size=2, n_groups=1,
                       cache_len=CACHE_LEN, **kw)


# ------------------------------------------------------------- policy
def test_policy_validation():
    with pytest.raises(ValueError):
        SchedulerPolicy(plan_size=0)
    with pytest.raises(ValueError):
        SchedulerPolicy(plan_min=5, plan_max=2)
    with pytest.raises(ValueError):
        SchedulerPolicy(plan_min=-1)
    with pytest.raises(ValueError):
        SchedulerPolicy(ema_alpha=0.0)
    with pytest.raises(ValueError):
        SchedulerPolicy(hysteresis=-0.1)
    with pytest.raises(ValueError):
        SchedulerPolicy(cost_mode="gpu")
    with pytest.raises(ValueError):
        SchedulerPolicy(replan_every=0)


def test_plan_rows_fixed_vs_dynamic():
    assert SchedulerPolicy(plan_size=3).plan_rows == 3
    assert SchedulerPolicy(plan_max=5).plan_rows == 5  # dynamic -> plan_max


def test_resolve_policy_precedence(setup):
    cfg, _ = setup
    # defaults when nothing is supplied
    assert resolve_policy(None) == SchedulerPolicy()
    # cfg.scheduler beats defaults
    via_cfg = SchedulerPolicy(plan_max=5)
    cfg2 = dataclasses.replace(cfg, scheduler=via_cfg)
    assert resolve_policy(cfg2) is via_cfg
    # explicit scheduler= beats cfg.scheduler
    explicit = SchedulerPolicy(plan_max=7)
    assert resolve_policy(cfg2, explicit) is explicit
    with pytest.raises(TypeError):
        resolve_policy(cfg, scheduler="not-a-policy")


def test_legacy_kwargs_fold_in_with_one_warning():
    th = TierThresholds(tau_hot=9, tau_cold=2)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        pol = resolve_policy(None, plan_size=3, thresholds=th)
    deps = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert len(deps) == 1
    assert "plan_size" in str(deps[0].message)
    assert pol.plan_size == 3 and pol.thresholds == th


def test_loop_legacy_kwargs_warn_and_resolve(setup):
    cfg, params = setup
    with pytest.warns(DeprecationWarning, match="plan_size"):
        loop = _loop(cfg, params, plan_size=2)
    assert loop.policy.plan_size == 2
    # the resolved policy threads through to the engine
    assert loop.engine.policy == loop.policy


def test_scheduler_threads_through_loop(setup):
    cfg, params = setup
    pol = SchedulerPolicy(plan_max=3, replan_every=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)  # no legacy path
        loop = _loop(cfg, params, scheduler=pol)
    assert loop.policy == pol
    assert loop.engine.policy == pol


# ----------------------------------------------------------- behavior
from repro.core.tiers import COLD, HOT, WARM  # noqa: E402


def _layout_tiers(eng):
    """Initial per-(layer, expert) tier placement of the live engine."""
    return np.stack([
        eng._read_tables(k)[0] for k in eng._layer_keys
    ])


def _steady_loads(tiers):
    """Per-expert loads that agree with the current placement under
    TierThresholds(tau_hot=6, tau_cold=1): decided == layout, so the
    planner has no moves."""
    return np.where(tiers == HOT, 9.0,
                    np.where(tiers == COLD, 0.5, 3.0)).astype(np.float64)


def test_hysteresis_zero_thrash_inside_band(setup):
    """Loads oscillating +-10% around tau_hot stay inside the 15%
    hysteresis band: tier decisions never flip, so no migrations and no
    thrash (the regression the bench's hysteresis leg gates on)."""
    cfg, params = setup
    pol = SchedulerPolicy(
        thresholds=TierThresholds(tau_hot=6, tau_cold=1),
        ema_alpha=1.0,  # EMA == instantaneous load: worst case for flicker
        hysteresis=0.15,
    )
    loop = _loop(cfg, params, scheduler=pol)
    eng = loop.engine
    tiers = _layout_tiers(eng)
    eng.replan(_steady_loads(tiers))  # settle decided onto the layout
    base_migrations = eng.stats.migrations
    for r in range(12):
        scale = 1.1 if r % 2 else 0.9
        eng.replan(scale * _steady_loads(tiers))
    assert eng.stats.migrations == base_migrations
    assert eng.stats.thrash_events == 0


def test_thrash_counter_fires_without_hysteresis(setup):
    """With hysteresis off, one expert whose load crosses tau_hot every
    replan is planned back into the tier it just left — that return
    move must be counted as thrash."""
    cfg, params = setup
    pol = SchedulerPolicy(
        thresholds=TierThresholds(tau_hot=6, tau_cold=1),
        ema_alpha=1.0,
        hysteresis=0.0,
        cost_mode="loads",  # no breakeven gate: every flip migrates
        plan_size=2,  # room for the flapper AND the displaced victim
    )
    loop = _loop(cfg, params, scheduler=pol)
    eng = loop.engine
    tiers = _layout_tiers(eng)
    assert (tiers == WARM).any(axis=1).all()
    flap = np.argmax(tiers == WARM, axis=1)  # one warm expert per layer
    rows = np.arange(tiers.shape[0])
    steady = _steady_loads(tiers)
    eng.replan(steady)
    assert eng.stats.migrations == 0  # settled: decided == layout
    for r in range(6):
        loads = steady.copy()
        loads[rows, flap] = 9.0 if r % 2 == 0 else 3.0
        eng.replan(loads)
    assert eng.stats.migrations > 0
    assert eng.stats.thrash_events > 0


def test_freeze_observes_but_never_migrates(setup):
    cfg, params = setup
    pol = SchedulerPolicy(
        thresholds=TierThresholds(tau_hot=6, tau_cold=1),
        ema_alpha=1.0, freeze=True,
    )
    loop = _loop(cfg, params, scheduler=pol)
    eng = loop.engine
    n_moe, e = eng.predictor.ema.shape
    for r in range(6):
        level = 50.0 if r % 2 else 0.1
        eng.replan(np.full((n_moe, e), level, np.float64))
    assert eng.stats.replans == 6  # plans drawn (and counted) ...
    assert eng.stats.migrations == 0  # ... but nothing ever moves
    assert float(eng.predictor.ema.max()) > 0  # observation still ran


def test_fixed_plan_size_caps_moves_per_layer(setup):
    cfg, params = setup
    pol = SchedulerPolicy(
        thresholds=TierThresholds(tau_hot=6, tau_cold=1),
        ema_alpha=1.0, cost_mode="loads", plan_size=2,
    )
    loop = _loop(cfg, params, scheduler=pol)
    eng = loop.engine
    n_moe, e = eng.predictor.ema.shape
    loads = np.full((n_moe, e), 3.0)
    loads[:, :3] = 50.0  # three experts per layer want HOT; cap is 2
    eng.replan(loads)
    assert eng.stats.migrations == 2 * n_moe


def test_dynamic_sizing_clamps_to_plan_max(setup):
    cfg, params = setup
    pol = SchedulerPolicy(
        thresholds=TierThresholds(tau_hot=6, tau_cold=1),
        ema_alpha=1.0, cost_mode="loads", plan_min=1, plan_max=2,
    )
    loop = _loop(cfg, params, scheduler=pol)
    eng = loop.engine
    n_moe, e = eng.predictor.ema.shape
    loads = np.full((n_moe, e), 3.0)
    loads[:, :4] = 50.0
    eng.replan(loads)
    assert 1 * n_moe <= eng.stats.migrations <= 2 * n_moe
