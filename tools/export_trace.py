"""Export observability artifacts from a traced smoke serving replay.

Runs the serving loop on a smoke-scale MoE config with
`ObsConfig(trace=True)` and writes three files:

  * a Chrome/Perfetto-loadable `trace_event` JSON (open it at
    https://ui.perfetto.dev or chrome://tracing) with the nested
    serving.{step,admit,prefill_chunk,decode,sync} and
    scheduler.{replan,migrate} spans, the
    kernel.<op> compile spans, the tier/{experts,predicted_load}
    counter tracks, and the tier_migration / thrash instants;
  * a metrics snapshot JSON — the loop's `MetricsRegistry.snapshot()`
    dict (serving.* / engine.* / predictor.* on one registry);
  * a Prometheus-style text dump of the same registry.

The replay forces migrations (smoke-scale tier thresholds +
`plan_min=1`, as the serving_bench --skew correctness leg does) so the
scheduler/tier channel is populated, then self-validates the exported
trace: structural `trace_event` checks, span containment per track,
and presence of the span/instant families the acceptance criteria
name. Exit status is nonzero on any failure, so CI can run this as the
nightly observability gate.

  PYTHONPATH=src python tools/export_trace.py --out serving.trace.json
  PYTHONPATH=src python tools/export_trace.py --check serving.trace.json

`--check PATH` validates an existing export (no replay, no jax
import) — use it against a downloaded CI artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# script mode: tools/ itself is not a package; src/ comes from PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# span families the exported timeline must carry (acceptance criteria:
# nested step/prefill/decode/replan spans + tier-migration instants)
REQUIRED_SPANS = ("serving.step", "serving.prefill_chunk", "serving.decode",
                  "scheduler.replan")
REQUIRED_INSTANTS = ("tier_migration",)


def check_trace(path: str) -> int:
    """Validate an exported trace file: well-formed trace_event JSON,
    spans nest per (pid, tid) track, required families present."""
    from repro.obs.trace import load_trace, validate_trace_events

    try:
        events = load_trace(path)
    except (OSError, ValueError) as e:
        print(f"[export_trace] FAIL: cannot load {path}: {e}")
        return 1
    problems = validate_trace_events(events)
    names = {str(e.get("name")) for e in events}
    for want in REQUIRED_SPANS:
        if want not in names:
            problems.append(f"missing required span family '{want}'")
    for want in REQUIRED_INSTANTS:
        if want not in names:
            problems.append(f"missing required instant family '{want}'")
    if not any(n.startswith("kernel.") for n in names):
        problems.append("no kernel.<op> spans on the timeline")
    if problems:
        print(f"[export_trace] FAIL: {path}: {len(problems)} problem(s)")
        for p in problems:
            print(f"[export_trace]   - {p}")
        return 1
    n_spans = sum(1 for e in events if e.get("ph") == "X")
    n_inst = sum(1 for e in events if e.get("ph") == "i")
    n_ctr = sum(1 for e in events if e.get("ph") == "C")
    print(f"[export_trace] ok: {path}: {len(events)} events "
          f"({n_spans} spans, {n_inst} instants, {n_ctr} counter samples), "
          f"{len(names)} distinct names")
    return 0


def run_replay(args) -> int:
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config, reduce_for_smoke
    from repro.core.policy import SchedulerPolicy
    from repro.core.tiers import TierThresholds
    from repro.models.model import init_params
    from repro.obs import ObsConfig
    from repro.serving.batching import Request
    from repro.serving.loop import ServingLoop

    cfg = reduce_for_smoke(get_config(args.arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(13)
    new_tokens = args.new_tokens
    cache_len = args.prompt_len + 8 + new_tokens + 2

    # smoke-scale thresholds + plan_min=1: per-step expert counts are
    # tiny, so the aggregated-batch defaults would classify everything
    # cold and the tier channel would have nothing to record
    policy = SchedulerPolicy(
        thresholds=TierThresholds(tau_hot=args.tau_hot,
                                  tau_cold=args.tau_cold),
        plan_min=1,
    )
    loop = ServingLoop(
        cfg, params, batch_size=args.batch, n_groups=args.groups,
        cache_len=cache_len,
        obs=ObsConfig(trace=True, trace_path=args.out),
        scheduler=policy,
    )
    # mixed prompt lengths so chunked prefill and admission interleave
    # with decode on the timeline
    for i in range(args.requests):
        plen = args.prompt_len + (i % 3) * 4
        loop.submit(Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=new_tokens,
        ))
    done = loop.run()
    trace_path = loop.obs.export_trace()
    print(f"[export_trace] served {len(done)}/{args.requests} requests; "
          f"wrote {trace_path}")

    snap = loop.obs.snapshot()
    with open(args.metrics_json, "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
        f.write("\n")
    with open(args.prom, "w") as f:
        f.write(loop.obs.prometheus_text())
    print(f"[export_trace] wrote {args.metrics_json} "
          f"({len(snap)} metrics) and {args.prom}")
    print(f"[export_trace] serving.tokens_per_s="
          f"{snap.get('serving.tokens_per_s', 0.0):.1f} "
          f"engine.migrations={snap.get('engine.migrations', 0)} "
          f"predictor.accuracy={snap.get('predictor.accuracy', 0.0):.3f}")

    rc = 0
    if len(done) != args.requests:
        print(f"[export_trace] FAIL: incomplete serve "
              f"({len(done)}/{args.requests})")
        rc = 1
    return check_trace(trace_path) or rc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", default=None, metavar="PATH",
                    help="validate an existing trace export and exit "
                         "(no replay)")
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--tau-hot", type=float, default=6.0,
                    help="hot-tier threshold for the replay policy "
                         "(smoke-scale, as serving_bench --skew)")
    ap.add_argument("--tau-cold", type=float, default=1.0)
    ap.add_argument("--out", default="serving.trace.json",
                    help="trace_event JSON output path (untracked "
                         "scratch — .gitignore'd, CI uploads it as an "
                         "artifact)")
    ap.add_argument("--metrics-json", default="metrics_snapshot.json",
                    help="MetricsRegistry.snapshot() dump path")
    ap.add_argument("--prom", default="metrics_snapshot.prom",
                    help="Prometheus-style text dump path")
    args = ap.parse_args(argv)
    if args.check:
        return check_trace(args.check)
    return run_replay(args)


if __name__ == "__main__":
    sys.exit(main())
