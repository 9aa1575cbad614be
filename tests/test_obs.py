"""Observability layer tests: the metrics registry and stat facades
(percentile edge cases, get-or-create typing, the accumulate-vs-reset
contract, shared-registry wiring across loop/engine/predictor), the
tracer (zero-cost when disabled, trace_event export round-trip with
span nesting under a smoke serving run), and the `resolve_obs`
precedence rule (explicit obs= > cfg.obs > defaults)."""
import dataclasses
import json
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduce_for_smoke
from repro.models.model import init_params
from repro.obs import (
    NULL_SPAN,
    MetricsRegistry,
    ObsConfig,
    Observability,
    Tracer,
    pct,
    resolve_obs,
)
from repro.obs.trace import load_trace, validate_trace_events
from repro.serving.batching import Request
from repro.serving.loop import LoopStats, ServingLoop


@pytest.fixture(scope="module")
def setup():
    cfg = reduce_for_smoke(get_config("granite-moe-1b-a400m"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _requests(cfg, n=4, new_tokens=3, seed=7):
    rng = np.random.default_rng(seed)
    return [
        Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, 4 + rid % 3)
            .astype(np.int32),
            max_new_tokens=new_tokens,
        )
        for rid in range(n)
    ]


def _serve(loop, reqs):
    for r in reqs:
        loop.submit(r)
    return loop.run(max_steps=500)


# ------------------------------------------------ percentile edge cases
def test_pct_empty_and_single_sample_no_warnings():
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any numpy warning fails the test
        assert pct([], 50) == 0.0
        assert pct([], 95) == 0.0
        assert pct([0.25], 50) == 0.25
        assert pct([0.25], 95) == 0.25
        assert pct([1.0, 3.0], 50) == 2.0


def test_stats_percentiles_defined_on_empty_and_single():
    st = LoopStats()
    assert st.ttft_p50_s == 0.0 and st.ttft_p95_s == 0.0
    assert st.itl_p50_s == 0.0 and st.plan_p95_s == 0.0
    st.ttft_s.append(0.5)
    assert st.ttft_p50_s == 0.5 and st.ttft_p95_s == 0.5


# -------------------------------------------------------- the registry
def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("x.n", unit="1", desc="a counter")
    assert reg.counter("x.n") is c  # get-or-create returns the same
    with pytest.raises(ValueError):
        reg.gauge("x.n")  # same name, different kind
    h = reg.histogram("x.lat_s", unit="s")
    h.append(0.1)
    h.append(0.3)
    c.add(2)
    snap = reg.snapshot()
    assert snap["x.n"] == 2
    assert snap["x.lat_s.count"] == 2
    assert snap["x.lat_s.p50"] == pytest.approx(0.2)
    assert "x.n" in reg and "nope" not in reg


def test_registry_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("serving.admitted", unit="requests", desc="admitted").add(3)
    reg.histogram("serving.ttft_s", unit="s").append(0.5)
    text = reg.prometheus_text()
    assert "# TYPE serving_admitted_requests counter" in text
    assert "serving_admitted_requests 3" in text
    assert 'quantile="0.5"' in text


def test_facade_reset_is_scoped_registry_reset_is_global():
    reg = MetricsRegistry()
    st = LoopStats(reg)
    other = reg.counter("other.n")
    st.admitted += 2
    st.wall_s += 1.5
    st.ttft_s.append(0.1)
    other.add(5)
    st.reset()  # facade reset: only serving.* instruments
    assert st.admitted == 0 and st.wall_s == 0.0 and st.ttft_s == []
    assert reg.snapshot()["other.n"] == 5
    reg.reset()  # registry reset: everything
    assert reg.snapshot()["other.n"] == 0


# ---------------------------------------- accumulate-vs-reset contract
def test_wall_s_accumulates_across_runs_and_reset_clears(setup):
    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=16)
    _serve(loop, _requests(cfg, n=2, seed=1))
    first = loop.stats.wall_s
    first_tokens = loop.stats.generated_tokens
    assert first > 0 and first_tokens > 0
    _serve(loop, _requests(cfg, n=2, seed=2))
    # documented contract: metrics ACCUMULATE across run() calls
    assert loop.stats.wall_s > first
    assert loop.stats.generated_tokens == 2 * first_tokens
    # the regression this guards: reset() starts a fresh window
    loop.stats.reset()
    assert loop.stats.wall_s == 0.0
    assert loop.stats.generated_tokens == 0
    _serve(loop, _requests(cfg, n=2, seed=3))
    assert loop.stats.wall_s > 0
    assert loop.stats.generated_tokens == first_tokens


# ------------------------------------------------------------- tracing
def test_disabled_tracer_is_null_and_empty():
    tr = Tracer(enabled=False)
    s = tr.span("serving.step", phase=1)
    assert s is NULL_SPAN  # shared singleton: no per-call allocation
    with s:
        pass
    tr.instant("x")
    tr.counter("y", {"v": 1.0})
    assert tr.events == []
    assert tr.to_trace_events() == [] or all(
        e.get("ph") == "M" for e in tr.to_trace_events()
    )


def test_loop_with_tracing_disabled_records_no_events(setup):
    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=16)
    _serve(loop, _requests(cfg, n=2))
    assert loop.obs.tracer.enabled is False
    assert loop.obs.tracer.events == []


def test_trace_export_round_trip(setup, tmp_path):
    cfg, params = setup
    path = str(tmp_path / "smoke.trace.json")
    loop = ServingLoop(cfg, params, batch_size=4, n_groups=2, cache_len=16,
                       obs=ObsConfig(trace=True, trace_path=path))
    done = _serve(loop, _requests(cfg, n=6))
    assert len(done) == 6
    loop.obs.export_trace()

    with open(path) as f:
        doc = json.load(f)  # must parse as plain JSON
    assert isinstance(doc["traceEvents"], list)
    events = load_trace(path)
    assert validate_trace_events(events) == []  # fields + nesting

    names = {e["name"] for e in events}
    for want in ("serving.step", "serving.admit", "serving.decode",
                 "scheduler.replan"):
        assert want in names, f"missing {want} span"
    # spans nest: every decode span lies inside some step span
    spans = {n: [(e["ts"], e["ts"] + e["dur"]) for e in events
                 if e.get("ph") == "X" and e["name"] == n]
             for n in ("serving.step", "serving.decode")}
    assert spans["serving.decode"]
    for s0, s1 in spans["serving.decode"]:
        assert any(t0 <= s0 and s1 <= t1 for t0, t1 in spans["serving.step"])


def test_kernel_spans_on_shared_timeline(setup, tmp_path):
    from repro.kernels.backend import set_kernel_tracer

    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=16,
                       obs=ObsConfig(trace=True))
    try:
        _serve(loop, _requests(cfg, n=2, seed=11))
        names = {e["name"] for e in loop.obs.tracer.events}
        kernel = {n for n in names if n.startswith("kernel.")}
        # op wrappers are jit'd: spans fire at trace/compile time, so a
        # fresh shape set compiles at least the paged attention ops
        assert kernel, f"no kernel.* spans among {sorted(names)}"
    finally:
        set_kernel_tracer(None)  # don't leak the process-global tracer


# ------------------------------------------------ shared registry wiring
def test_loop_engine_predictor_share_one_registry(setup):
    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=16)
    assert loop.stats.registry is loop.engine.stats.registry
    assert loop.stats.registry is loop.engine.predictor.stats.registry
    _serve(loop, _requests(cfg, n=2, seed=5))
    snap = loop.stats.snapshot()
    assert snap["serving.completed"] == 2
    assert snap["engine.steps"] > 0
    assert "predictor.accuracy" in snap


# --------------------------------------------------- resolve_obs rule
def test_resolve_obs_precedence(setup):
    cfg, _ = setup
    # defaults: metrics on, tracing off
    out = resolve_obs(cfg, None)
    assert isinstance(out, Observability) and not out.tracer.enabled
    # cfg.obs is used when no explicit obs=
    cfg_traced = dataclasses.replace(cfg, obs=ObsConfig(trace=True))
    assert resolve_obs(cfg_traced, None).tracer.enabled
    # explicit obs= beats cfg.obs
    explicit = Observability(ObsConfig(trace=False))
    assert resolve_obs(cfg_traced, explicit) is explicit
    # an Observability is adopted as-is (shared registry/tracer)
    assert resolve_obs(None, explicit).registry is explicit.registry
    with pytest.raises(TypeError):
        resolve_obs(cfg, obs="yes please")
    from repro.kernels.backend import set_kernel_tracer

    set_kernel_tracer(None)  # resolve_obs(cfg_traced) installed one


# ------------------------------------ spans on the profiler's clock
PROGRAM_SPANS = ("serving.step", "serving.admit", "serving.prefill_chunk",
                 "serving.decode", "serving.sync", "scheduler.replan",
                 "scheduler.migrate")


def _forced_migrations():
    from repro.core.policy import SchedulerPolicy
    from repro.core.tiers import TierThresholds

    # smoke-scale loads are tiny: these thresholds plus plan_min=1 make
    # the planner move experts on every replan
    return SchedulerPolicy(
        thresholds=TierThresholds(tau_hot=6.0, tau_cold=1.0), plan_min=1)


def _xplane_host_events(trace_dir):
    """(name, start_ns, end_ns) of every host event in the one profile
    written under `trace_dir`."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def _profiled_steps(loop, cfg, trace_dir, rid0):
    """Two iterations under the profiler, each with an admission, a
    prefill chunk, a decode step and a migration."""
    reqs = _requests(cfg, n=4, new_tokens=8, seed=rid0)
    for i, r in enumerate(reqs):
        r.rid = rid0 + i
    for r in reqs[:2]:
        loop.submit(r)
    steps = loop.stats.decode_steps
    for _ in range(50):  # until the first two decode, migrating
        if loop.stats.decode_steps >= steps + 2:
            break
        loop.step_once()
    for r in reqs[2:]:
        loop.submit(r)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(trace_dir), profiler_options=opts):
        for _ in range(2):
            loop.step_once()
    loop.run()
    return _xplane_host_events(trace_dir)


def test_spans_land_in_profiler_trace_only_when_enabled(setup, tmp_path):
    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=4, n_groups=1, cache_len=24,
                       prefill_chunk_tokens=2, scheduler=_forced_migrations())
    _profiled_steps(loop, cfg, tmp_path / "warm", 100)  # compile, untraced
    loop.obs.tracer.enabled = True
    events = _profiled_steps(loop, cfg, tmp_path / "on", 200)
    loop.obs.tracer.enabled = False
    names = {n for n, _, _ in events}
    for want in PROGRAM_SPANS:
        assert want in names, f"{want} missing from the profiler trace"
    steps = [(s, e) for n, s, e in events if n == "serving.step"]
    assert len(steps) == 2
    for name in ("serving.decode", "serving.sync"):
        inner = [(s, e) for n, s, e in events if n == name]
        assert inner
        for s0, s1 in inner:
            assert any(t0 <= s0 and s1 <= t1 for t0, t1 in steps), name

    off = _profiled_steps(loop, cfg, tmp_path / "off", 300)
    assert not {n for n, _, _ in off} & set(PROGRAM_SPANS)


# ------------------------------------------ counters of the MoE layers
def _device_tiers(eng):
    """[L, E] expert_tier tables read back from the device."""
    rows = []
    for kind, name, g in eng._layer_keys:
        if kind == "layer":
            rows.append(np.asarray(eng.tiered[name]["expert_tier"]))
        else:
            rows.append(np.asarray(eng.tiered["stack"][name]["expert_tier"])[g])
    return np.stack(rows)


def test_routed_split_follows_the_layout_each_step_ran_with(setup):
    cfg, params = setup
    loop = ServingLoop(cfg, params, batch_size=4, n_groups=1, cache_len=24,
                       scheduler=_forced_migrations())
    eng = loop.engine
    seen = []  # (counts, device tier tables before the step)
    orig = eng.step_slots_paged

    def step(*a, **kw):
        tiers = _device_tiers(eng)
        logits, counts = orig(*a, **kw)
        seen.append((counts, tiers))
        return logits, counts

    eng.step_slots_paged = step
    _serve(loop, _requests(cfg, n=4, new_tokens=6))
    st = eng.stats
    assert st.migrations > 0
    assert len({t.tobytes() for _, t in seen}) > 2  # the layout moved
    want = np.zeros(3)
    for counts, tiers in seen:
        want += np.bincount(tiers.ravel(), weights=np.asarray(counts).ravel(),
                            minlength=3)
    assert [st.routed_hot, st.routed_warm, st.routed_cold] == want.tolist()
    assert st.moe_steps == len(seen) == loop.stats.decode_steps
    assert st.routed_hot + st.routed_warm + st.routed_cold == st.moe_assignments


@pytest.mark.parametrize("arch", ["granite-moe-1b-a400m", "jamba-v0.1-52b"])
def test_expert_rows_and_assignments_by_hand_for_one_group(arch):
    """Also on a hybrid arch, whose expert counts carry a (zero) row for
    every non-MoE layer."""
    cfg = reduce_for_smoke(get_config(arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    loop = ServingLoop(cfg, params, batch_size=6, n_groups=2, cache_len=24)
    eng, kv = loop.engine, loop.kv
    w = 3  # rows of one zigzag group
    live = np.array([True, False, True])
    tables = np.full((w, kv.blocks_per_slot), kv.trash, np.int32)
    _, counts = eng.step_slots_paged(np.ones((w, 1), np.int32),
                                     np.array([4, 0, 9]), [0, 1, 2], tables,
                                     live=live)
    eng.observe(counts, eng.last_dispatch)
    counts = np.asarray(counts)[eng._moe_rows]
    n_moe, k = len(eng._layer_keys), cfg.moe.top_k
    assert n_moe == sum(cfg.uses_moe_layer(i) for i in range(cfg.n_layers))
    n_hot, n_warm, n_cold = eng.sizes
    cold_slots = -(-n_cold // 16) * 16  # init_tiered_state pads to 16
    # every tier slot runs at capacity w (cold: at least top_k)
    rows = n_moe * ((n_hot + n_warm) * w + cold_slots * max(k, w))
    st = eng.stats
    assert st.moe_steps == 1
    assert st.moe_assignments == 2 * k * n_moe  # two live tokens, k each
    assert st.expert_rows == rows
    assert st.experts_touched == int((counts > 0).sum())
    assert 0 < st.experts_touched <= st.moe_assignments


def test_queue_wait_one_sample_per_request(setup):
    cfg, params = setup
    # 2-token chunks: each prompt (4-6 tokens) takes several chunk calls
    loop = ServingLoop(cfg, params, batch_size=2, n_groups=1, cache_len=24,
                       prefill_chunk_tokens=2)
    reqs = _requests(cfg, n=5, new_tokens=2)
    _serve(loop, reqs)
    waits = loop.stats.queue_wait_s
    assert len(waits) == len(reqs)
    assert all(w >= 0 for w in waits)
    # 5 requests through 2 slots: the later ones waited for a slot
    assert max(waits) > min(waits)
