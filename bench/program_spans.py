"""Reduce the program's own spans (repro.obs) in a JAX profiler trace.

While the program's tracer is enabled, each of its spans (`serving.step`,
`serving.sync`, `scheduler.migrate`, ...) is also a host event of the
`.xplane.pb`, on the profiler's one clock with the device's ops. This
reads those spans and the device's op events, inside the harness's
`bench.window` annotation (or, where a trace has none, the extent of the
program's spans), and gives:

  spans             count and total ms of each span name;
  host_ms_per_iter  per `serving.step`: its length less the
                    `serving.sync` spans inside it (the host's own work
                    in an iteration, without its waits for tokens);
  sync_ms_per_iter  per `serving.step`: the `serving.sync` time inside it;
  idle_by_span      device-idle time (no op running) split by the
                    innermost program span open at each instant.

    python3 bench/program_spans.py <trace dir>   # newest .xplane.pb in it

prints the reduction as one JSON object.
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List

from trace_reduce import OPS_LINE, WINDOW, Ev, newest_xplane, union

PREFIXES = ("serving.", "scheduler.", "spec.")
STEP, SYNC = "serving.step", "serving.sync"
OUTSIDE = "outside program spans"


def is_program_span(name: str) -> bool:
    return name.startswith(PREFIXES)


def load_events(path: str) -> Dict[str, List[Ev]]:
    """The device's op events of every TPU plane, and the host's program
    spans and `bench.window` annotation."""
    from jax.profiler import ProfileData

    device, host = [], []
    for plane in ProfileData.from_file(path).planes:
        dev = plane.name.startswith("/device:TPU:") and "Core" not in plane.name[12:]
        if not dev and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if dev or is_program_span(ev.name) or ev.name == WINDOW:
                    (device if dev else host).append(Ev(
                        line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, {}))
    return {"device": device, "host": host}


def innermost_segments(spans: List[Ev], lo: float, hi: float) -> List[tuple]:
    """[(start, end, name)] covering [lo, hi]: at each instant the
    innermost of the nested `spans` open then, else OUTSIDE."""
    bounds = sorted({lo, hi} | {t for s in spans for t in (s.start, s.end) if lo < t < hi})
    out = []
    for a, b in zip(bounds, bounds[1:]):
        mid = (a + b) / 2.0
        best, best_len = OUTSIDE, float("inf")
        for s in spans:
            if s.start <= mid < s.end and s.end - s.start < best_len:
                best, best_len = s.name, s.end - s.start
        if out and out[-1][2] == best and out[-1][1] == a:
            out[-1] = (out[-1][0], b, best)
        else:
            out.append((a, b, best))
    return out


def reduce(events: Dict[str, List[Ev]]) -> dict:
    host = events["host"]
    spans = [h for h in host if is_program_span(h.name)]
    wins = [h for h in host if h.name == WINDOW]
    if wins:
        w0, w1 = wins[0].start, wins[0].end
    elif spans:
        w0, w1 = min(s.start for s in spans), max(s.end for s in spans)
    else:
        return {"window_ns": 0.0, "spans": {}, "steps": 0, "host_ms_per_iter": None,
                "sync_ms_per_iter": None, "idle_by_span": {}}
    spans = [s for s in spans if s.end > w0 and s.start < w1]
    table: Dict[str, dict] = {}
    for s in spans:
        t = table.setdefault(s.name, {"n": 0, "ms": 0.0})
        t["n"] += 1
        t["ms"] += (min(s.end, w1) - max(s.start, w0)) / 1e6
    steps = [s for s in spans if s.name == STEP and w0 <= s.start and s.end <= w1]
    syncs = [s for s in spans if s.name == SYNC]
    host_ns = sync_ns = 0.0
    for st in steps:
        inner = sum(y.end - y.start for y in syncs if st.start <= y.start and y.end <= st.end)
        host_ns += st.end - st.start - inner
        sync_ns += inner
    busy = union((max(d.start, w0), min(d.end, w1)) for d in events["device"]
                 if d.line == OPS_LINE and d.end > w0 and d.start < w1)
    idle: Dict[str, float] = {}
    segs = innermost_segments(spans, w0, w1)
    prev, i = w0, 0
    for s, e in busy + [(w1, w1)]:
        # the idle gap [prev, s), split over the segments it crosses
        while i < len(segs) and segs[i][1] <= prev:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < s:
            a, b = max(segs[j][0], prev), min(segs[j][1], s)
            if b > a:
                idle[segs[j][2]] = idle.get(segs[j][2], 0.0) + (b - a) / 1e9
            j += 1
        prev = max(prev, e)
    n = len(steps)
    return {
        "window_ns": w1 - w0,
        "spans": table,
        "steps": n,
        "host_ms_per_iter": host_ns / 1e6 / n if n else None,
        "sync_ms_per_iter": sync_ns / 1e6 / n if n else None,
        "idle_by_span": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
    }


def reduce_dir(trace_dir: str) -> dict:
    return reduce(load_events(newest_xplane(trace_dir)))


if __name__ == "__main__":
    print(json.dumps(reduce_dir(sys.argv[1])))
