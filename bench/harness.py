"""One run of one cell: build, warm up, fill, measure, check.

`run.py` is the command; this module is the run. It knows no cell,
configuration, architecture, mix or metric by name: the cell's entry in
BENCHMARK.json names its configuration file and traffic file, the
configuration names its architecture module in bench/references/, and
each per-layer metric is a reader in bench/metrics/<metric>.py.

The system under test is the program's `ServingLoop`, driven through
`submit` and `step_once` with its library defaults. The harness times
from its own side: every token is stamped on the host when `step_once`
returns (the loop has already pulled the sampled tokens to the host by
then), and time to first token runs from when a request was DUE, not
from when it was submitted.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
LOG = "[bench]"


def log(*args) -> None:
    print(LOG, *args, flush=True)


# ------------------------------------------------------------------ spec
def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(benchmark, workload entry, configuration dict, mix dict, check
    limits) of cell `name`, all found by name."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"{LOG} unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(ROOT, conf["file"])
    import generator

    mix = generator.load_mix(BENCH_DIR, cell["traffic"])
    check = load_json(BENCH_DIR, "checks", f"{name}.json")
    return bench, cell, cfg, mix, check


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py, loaded by path: a metric reader or an
    architecture module."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- counting
@contextlib.contextmanager
def compile_counter():
    """Backend compiles (persistent-cache loads included) and cache hits
    while open."""
    import jax

    c = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0, "longest_s": []}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            c["compiles"] += 1
            c["compile_s"] += secs
            c["longest_s"] = sorted(c["longest_s"] + [round(secs, 3)])[-6:]

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            c["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield c
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


@dataclasses.dataclass
class Tracked:
    """The harness's own record of one request."""

    req: object
    due: float  # absolute host time the request was due
    submitted: float = 0.0
    stamps: List[float] = dataclasses.field(default_factory=list)
    seen: int = 0


class Feeder:
    """Feeds a schedule into the loop and stamps every token.

    A schedule that starts `resident` requests in the steady state holds
    back the rest until every slot has taken one of them, so that the
    loop's admission cannot fill a slot from the backlog first."""

    def __init__(self, loop, schedule, t_arrivals: float):
        from repro.serving.batching import Request

        self.loop = loop
        self.pending = [
            Tracked(Request(rid=i, prompt=p, max_new_tokens=int(m)), t_arrivals + float(d))
            for i, (p, m, d) in enumerate(zip(schedule.prompts, schedule.max_new, schedule.due_s))
        ]
        self.pending.reverse()  # pop() from the end = earliest due
        self.inflight: List[Tracked] = []
        self.done: List[Tracked] = []
        self.all: List[Tracked] = []
        self.iterations = 0
        self.resident = schedule.resident
        self.released = schedule.resident == 0

    def submit_due(self, now: float) -> None:
        while self.pending and self.pending[-1].due <= now:
            if not self.released and len(self.all) >= self.resident:
                if any(s.request is None for s in self.loop.batcher.slots):
                    return
                self.released = True
            t = self.pending.pop()
            t.submitted = time.perf_counter()
            self.loop.submit(t.req)
            self.inflight.append(t)
            self.all.append(t)

    def step(self) -> None:
        self.loop.step_once()
        now = time.perf_counter()
        self.iterations += 1
        still = []
        for t in self.inflight:
            n = len(t.req.generated)
            if n > t.seen:
                t.stamps.extend([now] * (n - t.seen))
                t.seen = n
            (self.done if t.req.done else still).append(t)
        self.inflight = still

    def next_due(self) -> Optional[float]:
        return self.pending[-1].due if self.pending else None

    def steady(self) -> bool:
        """A closed backlog is in its steady state once every resident
        request has its first token."""
        return self.released and all(t.req.generated for t in self.all[: self.resident])


# ---------------------------------------------------------------- warmup
def warm_up(loop, mix) -> None:
    """Compile every program shape the window can use, and no others:
    decode at each power-of-two block-table width the mix's contexts
    reach, each prefill chunk width at each table width its prompts
    reach, the migration swap, and the small eager ops around them.
    Writes land in the pool's trash block; nothing is admitted."""
    import jax
    import jax.numpy as jnp

    import generator
    from repro.kernels.paged_attention import active_block_width

    eng, kv = loop.engine, loop.kv
    bs, bps = kv.block_size, max(1, kv.blocks_per_slot)
    rows = loop.batcher.batch_size // loop.batcher.n_groups
    trash_tables = np.full((rows, bps), kv.trash, np.int32)

    def ladder(lo_pos, hi_pos):
        lo, hi = active_block_width(lo_pos, bs, bps), active_block_width(hi_pos, bs, bps)
        w, out = lo, []
        while True:
            out.append(w)
            if w >= hi:
                return out
            w = min(w * 2, bps)

    min_p, max_p = generator.min_prompt_len(mix), generator.max_prompt_len(mix)
    max_ctx = generator.cache_len(mix) - 1
    for w in ladder(min_p, max_ctx):
        pos = np.zeros(rows, np.int64)
        pos[0] = w * bs - 1
        logits, _ = eng.step_slots_paged(
            np.zeros((rows, 1), np.int32), pos, list(range(rows)), trash_tables,
            live=np.ones(rows, bool),
        )
        np.asarray(jnp.argmax(logits, -1))
    budget = loop.prefill_chunk_tokens
    widths = sorted({loop.bucket_table.bucket_of(n) for n in range(1, min(budget, max_p) + 1)})
    prev = 0
    for c in widths:
        n_min = prev + 1
        for tw in ladder(min(budget, min_p) - 1, max_p - 1):
            end = tw * bs if tw < bps else kv.seq_len
            if end < n_min:
                continue
            n = min(c, end)
            eng.prefill_slots_paged(np.zeros((1, c), np.int32), [0], [n], [end - n])
        prev = c
    for nr in range(1, eng.prefill_rows + 1):
        logits = eng.prefill_slots_paged(
            np.zeros((nr, widths[0]), np.int32), list(range(nr)), [1] * nr, [0] * nr)
        for r in range(nr):
            np.asarray(jnp.argmax(logits[r], -1))
    # one no-op swap per tier-buffer structure: a stacked slot and an
    # unrolled layer compile apart
    noop = np.full((loop.policy.plan_rows, 5), -1, np.int32)
    keys = {("stack", next(iter(sub)), 0) if top == "stack" else ("layer", top, 0)
            for top, sub in eng.tiered.items()}
    eng.apply_planned([(key, noop) for key in sorted(keys)])
    jax.block_until_ready((kv.pools, eng.tiered))


def prewarm(loop, prompts) -> None:
    """Serve each prompt once (one output token) through the loop, so the
    radix prefix cache holds it before arrivals start."""
    from repro.serving.batching import Request

    for i, p in enumerate(prompts):
        loop.submit(Request(rid=-1 - i, prompt=p, max_new_tokens=1))
    if prompts:
        loop.run()


# --------------------------------------------------------------- tracing
class WorkLog:
    """Attention work and prefill tokens of the engine calls made while
    recording, read from the calls' own arguments."""

    def __init__(self, eng):
        self.on = False
        self.decode_ctx: List[np.ndarray] = []
        self.prefill_rows: List[tuple] = []  # (past, length)
        orig_step, orig_prefill = eng.step_slots_paged, eng.prefill_slots_paged
        orig_apply, orig_plan = eng.apply_planned, eng.plan_migrations
        import jax

        ann = jax.profiler.TraceAnnotation

        def step(tokens, pos, slot_indices, tables, live=None):
            if self.on:
                lv = np.ones(len(pos), bool) if live is None else np.asarray(live, bool)
                self.decode_ctx.append(np.asarray(pos, np.int64)[lv] + 1)
            with ann("bench.decode_call"):
                return orig_step(tokens, pos, slot_indices, tables, live=live)

        def prefill(suffixes, slot_indices, lengths, past_len):
            if self.on:
                for p, n in zip(np.asarray(past_len), np.asarray(lengths)):
                    self.prefill_rows.append((int(p), int(n)))
            with ann("bench.prefill_call"):
                return orig_prefill(suffixes, slot_indices, lengths, past_len)

        def apply(plans):
            with ann("bench.migrate_call"):
                return orig_apply(plans)

        def plan():
            with ann("bench.plan_call"):
                return orig_plan()

        eng.step_slots_paged, eng.prefill_slots_paged = step, prefill
        eng.apply_planned, eng.plan_migrations = apply, plan


def profile_options():
    """Device trace and host TraceMe events; the Python call tracer is off
    (it would time every Python call of the loop and slow the host)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def counters(loop) -> Dict[str, float]:
    st, kvs = loop.stats, loop.kv.stats
    return {
        "serving.migrations": st.migrations,
        "serving.prefill_chunks": st.prefill_chunks,
        "serving.decode_steps": st.decode_steps,
        "serving.admitted": st.admitted,
        "kv.hit_tokens": kvs.hit_tokens,
        "kv.lookup_tokens": kvs.lookup_tokens,
    }


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


# ------------------------------------------------------------------- run
def pct(xs, q) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float,
             cfg_override: Optional[dict] = None, out_dir: Optional[str] = None,
             sabotage=None, control: bool = False) -> dict:
    """Run cell `name` once and return the result object the command
    prints. `cfg_override` replaces configuration keys (CPU rehearsals
    at a small size); `sabotage(loop)` lets a test break the timed path
    underneath to see the check fail. With `control` the check compares
    the lower-precision control (the tokens that the reference one
    precision down puts first, at the served positions) in the program's
    place, against the same limits: it has to come out not correct."""
    import jax

    import generator
    from repro.launch.serve import enable_compile_cache
    from repro.serving.loop import ServingLoop
    from repro.serving.tiered_moe import tier_sizes

    bench, cell, cfg, mix, check = load_cell(name)
    if cfg_override:
        cfg = {**cfg, **cfg_override}
    arch = load_module("references", cfg["reference"])
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    devices = jax.devices()
    log(f"device platform={dev.platform} kind={dev.device_kind!r} count={len(devices)} "
        f"jax={jax.__version__}")
    log(f"cell {name}: config {cfg['name']} traffic {cell['traffic']} seed {seed} "
        f"seconds {seconds} trace {int(trace)}")

    phases = {"start": time.perf_counter() - t_process}
    with compile_counter() as cc:
        t_ph = time.perf_counter()
        mcfg = arch.model_config(cfg)
        sizes = tier_sizes(mcfg)
        params, tiered = arch.program_weights(seed, cfg, mcfg, sizes)
        jax.block_until_ready((params, tiered))
        phases["weights"] = time.perf_counter() - t_ph
        cache_len = generator.cache_len(mix)
        slots = cfg["deployment"]["slots"]
        loop = ServingLoop(mcfg, params, tiered, batch_size=slots,
                           cache_len=cache_len, sizes=sizes)
        del params, tiered
        log(f"tiers hot/warm/cold={tuple(sizes)} slots={slots} cache_len={cache_len} "
            f"backends paged_attn={tuple(loop.engine.paged_attn_backend)} "
            f"moe={tuple(loop.engine.moe_backend)}")
        worklog = WorkLog(loop.engine) if trace else None
        t_ph = time.perf_counter()
        warm_up(loop, mix)
        phases["warm_up"] = time.perf_counter() - t_ph
        if sabotage is not None:
            sabotage(loop)
        warm_s = float(mix.get("warm_s", 0))
        sched = generator.generate(mix, cfg["vocab_size"], seed,
                                   horizon_s=warm_s + seconds + 60, slots=slots)
        t_ph = time.perf_counter()
        prewarm(loop, sched.warm_prompts)
        phases["prewarm"] = time.perf_counter() - t_ph
        t_arr = time.perf_counter()
        drv = Feeder(loop, sched, t_arr)
        fill_iters = 0
        while True:
            now = time.perf_counter()
            drv.submit_due(now)
            if sched.closed:
                if drv.steady() and loop.batcher.queue:
                    break
            elif now >= t_arr + warm_s:
                break
            if not drv.inflight and not sched.closed:
                nd = drv.next_due()
                time.sleep(max(0.0, min(0.005, (nd or now) - now)))
                continue
            drv.step()
            fill_iters += 1
        jax.block_until_ready((loop.kv.pools, loop.engine.tiered))
        setup_compiles = dict(cc)
    t_open = time.perf_counter()
    phases["fill"] = t_open - t_arr
    setup_s = t_open - t_process
    log(f"set-up {setup_s!r} s: fill iterations {fill_iters}, compiles {setup_compiles['compiles']} "
        f"({setup_compiles['compile_s']!r} s, longest {setup_compiles['longest_s']}), "
        f"persistent-cache hits {setup_compiles['cache_hits']}; phases "
        f"{json.dumps({k: round(v, 3) for k, v in phases.items()})}")

    # ---------------------------------------------------------- window
    log(f"contexts of the slots at window open: {context_spread(loop)}")
    c_open = counters(loop)
    it_open = drv.iterations
    trace_dir = None
    tr_info = {}
    trace_s = min(4.0, seconds / 2) if trace else 0.0
    trace_lead = min(1.0, seconds / 4)
    with compile_counter() as wc:
        tracing = False
        while True:
            now = time.perf_counter()
            if now >= t_open + seconds:
                t_close = now  # the window is all the time until here
                break
            if trace and not tracing and not tr_info and now >= t_open + trace_lead:
                jax.block_until_ready((loop.kv.pools, loop.engine.tiered))
                trace_dir = out_dir or os.path.join(ROOT, ".bench_trace")
                jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
                tracing = True
                worklog.on = True
                tr_info = {"c0": counters(loop), "t0": time.perf_counter()}
                ann = jax.profiler.TraceAnnotation("bench.window")
                ann.__enter__()
            if tracing and now >= tr_info["t0"] + trace_s:
                jax.block_until_ready((loop.kv.pools, loop.engine.tiered))
                ann.__exit__(None, None, None)
                tr_info["c1"] = counters(loop)
                worklog.on = False
                jax.profiler.stop_trace()
                tracing = False
            if tracing:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    drv.submit_due(now)
            else:
                drv.submit_due(now)
            if not drv.inflight:
                nd = drv.next_due()
                time.sleep(max(0.0, min(0.005, (nd or now) - now)))
                continue
            if tracing:
                with jax.profiler.TraceAnnotation("bench.step"):
                    drv.step()
            else:
                drv.step()
        if tracing:  # the window ended inside the traced span
            jax.block_until_ready((loop.kv.pools, loop.engine.tiered))
            ann.__exit__(None, None, None)
            tr_info["c1"] = counters(loop)
            worklog.on = False
            jax.profiler.stop_trace()
    window_s = t_close - t_open
    c_close = counters(loop)
    iters = drv.iterations - it_open
    log(f"contexts of the slots at window close: {context_spread(loop)}; admitted in window "
        f"{c_close['serving.admitted'] - c_open['serving.admitted']}, prefill chunks in window "
        f"{c_close['serving.prefill_chunks'] - c_open['serving.prefill_chunks']}, migrations in "
        f"window {c_close['serving.migrations'] - c_open['serving.migrations']}")
    log(f"window {window_s!r} s: iterations {iters}, compiles in window {wc['compiles']} "
        f"({wc['compile_s']!r} s), persistent-cache loads in window {wc['cache_hits']}")
    if wc["compiles"] or wc["cache_hits"]:
        log("WARNING: the window compiled or loaded programs; it is not a steady window")

    # --------------------------------------------------- end to end
    in_win = lambda s: t_open < s <= t_close  # noqa: E731
    tokens = sum(sum(1 for s in t.stamps if in_win(s)) for t in drv.all)
    itl = []
    for t in drv.all:
        st = [s for s in t.stamps if in_win(s)]
        itl.extend(np.diff(st).tolist())
    due_in = [t for t in drv.all if t_open <= t.due < t_close]
    ttft = [((t.stamps[0] if t.stamps and t.stamps[0] <= t_close else t_close) - t.due)
            for t in due_in]
    late = [t.submitted - t.due for t in drv.all if t.due >= t_open]
    if late:
        log(f"generator lateness (submit - due) p50 {pct(late, 50)!r} s max {max(late)!r} s "
            f"over {len(late)} requests")
    # requests due in the window, and requests served during it
    attempted = sum(1 for t in drv.all
                    if t_open <= t.due < t_close or any(in_win(s) for s in t.stamps))
    vocab = cfg["vocab_size"]
    failed = sum(
        1 for t in drv.all
        if len(t.req.generated) > t.req.max_new_tokens
        or any(not (0 <= g < vocab) for g in t.req.generated)
    )
    got_first = sum(1 for t in due_in if t.stamps and t.stamps[0] <= t_close)
    log(f"open loop: due in window {len(due_in)}, first token by close {got_first}, "
        f"ttft p50 {pct(ttft, 50)!r} s p90 {pct(ttft, 90)!r} s, queued at close "
        f"{len(loop.batcher.queue)}")
    log(f"tokens in window {tokens}, requests finished in window "
        f"{sum(1 for t in drv.done if in_win(t.stamps[-1]))}, due in window {len(due_in)}, "
        f"itl samples {len(itl)}, in flight at close {len(drv.inflight)}")
    # every end-to-end metric a cell may declare; a cell reports the ones
    # BENCHMARK.json gives it
    e2e = {
        "tokens_per_s": tokens / window_s if window_s > 0 else None,
        "ttft_p50_ms": pct(ttft, 50) * 1e3 if ttft else None,
        "ttft_p90_ms": pct(ttft, 90) * 1e3 if ttft else None,
        "itl_p50_ms": pct(itl, 50) * 1e3 if itl else None,
        "itl_p95_ms": pct(itl, 95) * 1e3 if itl else None,
        "setup_s": setup_s,
    }
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    # ---------------------------------------------------- per layer
    breakdown = None
    metrics: Dict[str, dict] = {}
    for m in [] if trace else bench["end_to_end"]:
        if name in m.get("workloads", [name]) and e2e[m["name"]] is not None:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    if trace and tr_info:
        import peaks
        import trace_reduce
        import work

        red = trace_reduce.reduce_dir(trace_dir)
        peak = peaks.peaks(dev.device_kind)
        ctx = {
            "cfg": cfg, "peak": peak, "trace": red,
            "counters_trace": _delta(tr_info["c0"], tr_info["c1"]),
            "counters_window": _delta(c_open, c_close), "iters_window": iters,
            "decode_ctx": worklog.decode_ctx, "prefill_rows": worklog.prefill_rows,
            "work": work,
        }
        readers = {m["name"]: load_module("metrics", m["name"]) for m in bench["per_layer"]
                   if name in m.get("workloads", [name])}
        for mname, mod in readers.items():
            unit = next(m["unit"] for m in bench["per_layer"] if m["name"] == mname)
            val = mod.read(ctx)
            log(f"per-layer {mname} = {val!r} {unit}")
            if val is not None:
                metrics[mname] = {"value": val, "unit": unit}
        device["busy_s"] = red["busy_ns"] / 1e9
        device["window_s"] = red["window_ns"] / 1e9
        breakdown = trace_reduce.breakdown(red)
        log(f"trace: busy {device['busy_s']!r} s of {device['window_s']!r} s; "
            f"modules {json.dumps(trace_reduce.module_table(red))}")

    # ---------------------------------------------------- correctness
    finished = [t for t in drv.done if in_win(t.stamps[-1])]
    sample = sample_for_check(finished, drv.inflight, seed, int(check["sample_requests"]))
    seqs = [(t.req.prompt, np.asarray(t.req.generated, np.int32)) for t in sample]
    n_served = sum(len(s) for _, s in seqs)
    del loop, drv, sample
    gc.collect()

    t_ref = time.perf_counter()
    ref = arch.Reference(cfg)
    gaps = arch.served_gaps(ref, seed, seqs, cache_len)
    ref_s = time.perf_counter() - t_ref
    floor = check["gap_vs_lower"]["floor"]
    served = gap_numbers(gaps["served"], gaps["lower"], floor)
    lower = gap_numbers(gaps["lower"], gaps["lower"], floor)
    log(f"served gaps {json.dumps(served)}; one precision down at the same positions "
        f"{json.dumps(lower)}")
    compared = served
    if control:
        compared = lower
        log("the control stands in the program's place")
    numbers = {k: {"value": compared[k], "limit": v["limit"]}
               for k, v in check.items() if isinstance(v, dict) and "limit" in v}
    correct = bool(all(n["value"] <= n["limit"] for n in numbers.values())
                   and failed == 0 and n_served > 0)
    log(f"reference over {len(seqs)} requests, {n_served} served tokens, {ref_s!r} s")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["served"] = served
    out["check"] = numbers
    for k, v in numbers.items():
        print(f"{LOG} check {k} = {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    print(f"{LOG} check served_tokens = {n_served} (limit > 0); failed requests = {failed} "
          f"(limit 0)", file=sys.stderr, flush=True)
    return out


def context_spread(loop) -> str:
    """Percentiles of the tokens each occupied slot holds (prompt and
    output so far)."""
    ctx = [r.prompt_len + len(r.generated) for r in
           (s.request for s in loop.batcher.slots) if r is not None]
    if not ctx:
        return "no slot occupied"
    q = np.percentile(ctx, [0, 10, 50, 90, 100]).astype(int).tolist()
    return f"{len(ctx)} slots, min/p10/p50/p90/max {q}"


def gap_numbers(g, lower, floor: float) -> dict:
    """The numbers of one set of gaps (logits below the reference's
    best, one per served token). `gap_vs_lower` is their sum as a share
    of the summed gaps of the tokens that the reference one precision
    down puts first at the same positions (`lower`), plus `floor`: how
    peaked a seed's random model is sets the scale of every gap, and the
    share divides it out; the floor, a gap or two of one near-tie, keeps
    a seed on which hardly any position is near a tie from deciding on
    one token."""
    g, lower = np.asarray(g, np.float64), np.asarray(lower, np.float64)
    if not len(g):
        return {"max_logit_gap": float("inf"), "p99_logit_gap": float("inf"),
                "mean_logit_gap": float("inf"), "gap_vs_lower": float("inf")}
    return {"max_logit_gap": float(g.max()), "p99_logit_gap": float(np.percentile(g, 99)),
            "mean_logit_gap": float(g.mean()),
            "gap_vs_lower": float(g.sum()) / (float(lower.sum()) + floor)}


def sample_for_check(finished, inflight, seed: int, n: int):
    """The `n` requests the check replays: the longest that finished in
    the window, then others drawn from the seed; in-flight requests, the
    longest first, fill in when too few finished."""
    rng = np.random.default_rng(seed + 7)
    pool = sorted(finished, key=lambda t: -len(t.req.generated))
    chosen = pool[:1]
    rest = pool[1:]
    rng.shuffle(rest)
    chosen += rest[: n - 1]
    if len(chosen) < n:
        extra = sorted((t for t in inflight if t.req.generated), key=lambda t: -len(t.req.generated))
        chosen += extra[: n - len(chosen)]
    return chosen
