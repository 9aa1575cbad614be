"""Reduce a JAX profiler trace (.xplane.pb) to the numbers the per-layer
readers and the result's `breakdown` use.

Two stages, so the reduction can be checked on events written by hand:

  load_events(path)  -> {"device": [Ev], "host": [Ev]}: the device's op
                        and module events and the host's `bench.*`
                        annotations, on the profiler's one clock;
  reduce(events)     -> busy time (union of op intervals), time and
                        calls per XLA module and per op, the idle gaps
                        and what the host was doing in each, all inside
                        the harness's `bench.window` annotation.

Module names are mapped to the program's layers by the table in
bench/module_layers.json.
"""
from __future__ import annotations

import bisect
import glob
import json
import os
import re
from typing import Dict, List, NamedTuple, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Ev(NamedTuple):
    line: str
    name: str
    start: float  # ns
    end: float  # ns
    stats: dict


def module_layers() -> dict:
    with open(os.path.join(BENCH_DIR, "module_layers.json")) as f:
        return json.load(f)["modules"]


def base_name(name: str) -> str:
    """`jit_step_paged(12)` -> `jit_step_paged`."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_label(text: str) -> str:
    """Short label of a device op event, whose name is the HLO
    instruction's text (`%copy.67 = bf16[24,16,3,1024,512]{...} copy(...)`):
    the instruction name and its result type without layout."""
    m = re.match(r"%?([^ =]+) = ([a-z0-9]+\[[0-9,]*\])?", text)
    if not m:
        return text[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


# control-flow ops whose events enclose the ops of their bodies
ENCLOSING = ("while", "conditional", "call")


def layer_of(module: str, table: Optional[dict] = None) -> str:
    table = module_layers() if table is None else table
    return table.get(base_name(module), "other")


# ------------------------------------------------------------------ load
KEEP_STATS = ("hlo_module", "hlo_op", "long_name", "tf_op", "hlo_category", "program_id")


def _stats(ev) -> dict:
    out = {}
    try:
        for k, v in ev.stats:
            if k in KEEP_STATS:
                out[k] = v
    except (TypeError, ValueError):
        pass
    return out


def load_events(path: str) -> Dict[str, List[Ev]]:
    """Device op/module events of every TPU plane and the host's
    `bench.*` annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name[12:]:
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    device.append(Ev(line.name, ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns, _stats(ev)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append(Ev(line.name, ev.name, ev.start_ns,
                                       ev.start_ns + ev.duration_ns, {}))
    return {"device": device, "host": host}


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_dir(trace_dir: str) -> dict:
    return reduce(load_events(newest_xplane(trace_dir)))


# ---------------------------------------------------------------- reduce
def union(intervals) -> List[tuple]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def overlap(intervals, lo, hi) -> float:
    """Length of the part of sorted disjoint `intervals` inside [lo, hi]."""
    tot = 0.0
    for s, e in intervals:
        if e <= lo:
            continue
        if s >= hi:
            break
        a, b = _clip(s, e, lo, hi)
        tot += b - a
    return tot


def reduce(events: Dict[str, List[Ev]]) -> dict:
    host = events["host"]
    wins = [h for h in host if h.name == WINDOW]
    if not wins:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    w0, w1 = wins[0].start, wins[0].end
    ops = [d for d in events["device"] if d.line == OPS_LINE and d.end > w0 and d.start < w1]
    mods = [d for d in events["device"] if d.line == MODULES_LINE and d.end > w0 and d.start < w1]
    busy = union(_clip(d.start, d.end, w0, w1) for d in ops)
    busy_ns = sum(e - s for s, e in busy)

    modules: Dict[str, dict] = {}
    for d in mods:
        a, b = _clip(d.start, d.end, w0, w1)
        m = modules.setdefault(base_name(d.name), {"ns": 0.0, "n": 0, "busy_ns": 0.0})
        m["ns"] += b - a
        m["n"] += 1
    # device time of a module = op time inside its executions (a module
    # event spans its launch to its end, gaps included)
    mod_iv = sorted((_clip(d.start, d.end, w0, w1), base_name(d.name)) for d in mods)
    starts = [iv[0][0] for iv in mod_iv]
    op_time: Dict[str, float] = {}
    for d in ops:
        label = op_label(d.name)
        if label.split(".")[0] in ENCLOSING:
            continue
        a, b = _clip(d.start, d.end, w0, w1)
        i = bisect.bisect_right(starts, a) - 1
        mod = mod_iv[i][1] if i >= 0 and mod_iv[i][0][1] >= b else "?"
        key = f"{mod}:{label}"
        op_time[key] = op_time.get(key, 0.0) + (b - a)
    for (s, e), name in mod_iv:
        modules[name]["busy_ns"] += overlap(busy, s, e)

    gaps = []
    prev = w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    steps = sorted((h.start, h.end) for h in host if h.name == "bench.step")
    idle_in_steps = sum(overlap(steps, s, e) for s, e in gaps)
    return {
        "window_ns": w1 - w0,
        "busy_ns": busy_ns,
        "modules": modules,
        "op_time_ns": op_time,
        "op_events": [(d.name, d.start, d.end, d.stats) for d in ops],
        "gaps": [(s, e, host_activity(host, s, e)) for s, e in gaps],
        "idle_in_steps_ns": idle_in_steps,
        "steps": len([s for s in steps if w0 <= s[0] < w1]),
    }


def host_activity(host: List[Ev], s: float, e: float) -> str:
    """What the host was doing over a device gap: the innermost
    `bench.*` annotation (other than the window) at the gap's middle."""
    mid = (s + e) / 2.0
    best, best_len = "host outside bench annotations", float("inf")
    for h in host:
        if h.name != WINDOW and h.start <= mid <= h.end and h.end - h.start < best_len:
            best, best_len = h.name, h.end - h.start
    return best


# ------------------------------------------------------------ breakdown
def module_table(red: dict, table: Optional[dict] = None) -> dict:
    table = module_layers() if table is None else table
    return {
        name: {"layer": table.get(name, "other"), "device_s": m["busy_ns"] / 1e9, "calls": m["n"]}
        for name, m in sorted(red["modules"].items(), key=lambda kv: -kv[1]["busy_ns"])
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """Top device operations by time and the longest idle time by host
    activity, in seconds, as the result line carries them."""
    ops = sorted(red["op_time_ns"].items(), key=lambda kv: -kv[1])[:top]
    idle: Dict[str, float] = {}
    for s, e, what in red["gaps"]:
        idle[what] = idle.get(what, 0.0) + (e - s)
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, t / 1e9] for n, t in ops],
        "idle_gaps": [[n, t / 1e9] for n, t in gaps],
    }
