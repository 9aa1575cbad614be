"""The reduction of the program's spans (bench/program_spans.py) on
events written by hand, and the device-trace reduction's output on the
recorded v5e trace pinned as it was before the program's spans joined
the profile."""
import hashlib
import json
import os

import pytest

import program_spans as ps
import trace_reduce as tr
from trace_reduce import Ev

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb")


def _events():
    host = [
        Ev("main", "bench.window", 0, 200, {}),
        Ev("main", "serving.step", 0, 100, {}),
        Ev("main", "serving.admit", 0, 10, {}),
        Ev("main", "serving.decode", 10, 90, {}),
        Ev("main", "scheduler.migrate", 20, 30, {}),
        Ev("main", "serving.sync", 40, 80, {}),
        Ev("main", "serving.step", 120, 190, {}),
        Ev("main", "serving.sync", 150, 170, {}),
        Ev("main", "serving.step", 195, 230, {}),  # ends after the window
        Ev("main", "bench.step", 0, 100, {}),  # the harness's own: not read
    ]
    dev = [
        Ev(tr.OPS_LINE, "fusion.1", 5, 25, {}),
        Ev(tr.OPS_LINE, "copy.2", 30, 60, {}),
        Ev(tr.OPS_LINE, "custom-call.3", 130, 180, {}),
        Ev(tr.MODULES_LINE, "jit_step_paged(1)", 0, 200, {}),  # not an op
    ]
    return {"device": dev, "host": host}


def test_span_table_counts_inside_the_window():
    red = ps.reduce(_events())
    assert red["window_ns"] == 200
    assert red["spans"]["serving.step"] == {"n": 3, "ms": pytest.approx((100 + 70 + 5) / 1e6)}
    assert red["spans"]["serving.sync"]["n"] == 2
    assert "bench.step" not in red["spans"]


def test_host_and_sync_per_iteration():
    red = ps.reduce(_events())
    # whole steps inside the window: [0,100] with 40 ns of sync, [120,190]
    # with 20
    assert red["steps"] == 2
    assert red["host_ms_per_iter"] == pytest.approx((60 + 50) / 2 / 1e6)
    assert red["sync_ms_per_iter"] == pytest.approx((40 + 20) / 2 / 1e6)


def test_idle_split_by_innermost_span():
    red = ps.reduce(_events())
    # busy [5,25] [30,60] [130,180]; idle [0,5] [25,30] [60,130] [180,200]
    idle = red["idle_by_span"]
    assert idle["serving.admit"] == pytest.approx(5e-9)  # [0,5]
    assert idle["scheduler.migrate"] == pytest.approx(5e-9)  # [25,30]
    assert idle["serving.sync"] == pytest.approx(20e-9)  # [60,80]
    assert idle["serving.decode"] == pytest.approx(10e-9)  # [80,90]
    # [90,100], [120,130], [180,190] and [195,200]: in a step, outside
    # its children
    assert idle["serving.step"] == pytest.approx(10e-9 + 10e-9 + 10e-9 + 5e-9)
    assert idle[ps.OUTSIDE] == pytest.approx(20e-9 + 5e-9)  # [100,120] [190,195]
    assert sum(idle.values()) == pytest.approx((200 - 20 - 30 - 50) / 1e9)
    assert list(idle)[0] == "serving.step"  # the longest first


def test_innermost_segments_cover_the_range():
    spans = [Ev("m", "serving.step", 10, 50, {}), Ev("m", "serving.sync", 20, 30, {})]
    assert ps.innermost_segments(spans, 0, 60) == [
        (0, 10, ps.OUTSIDE), (10, 20, "serving.step"), (20, 30, "serving.sync"),
        (30, 50, "serving.step"), (50, 60, ps.OUTSIDE)]


def test_no_program_spans_reads_nothing():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if not ps.is_program_span(h.name)]
    red = ps.reduce(ev)
    assert red["steps"] == 0 and red["host_ms_per_iter"] is None
    assert red["spans"] == {}
    assert list(red["idle_by_span"]) == [ps.OUTSIDE]


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_device_reduction_unchanged_on_recorded_trace():
    red = tr.reduce(tr.load_events(DATA))
    digest = hashlib.sha256(json.dumps(red, sort_keys=True).encode()).hexdigest()
    assert digest == "774494d62d764f8d0efba7e9693b0a1b5f7a15a635fe808b43523cbd3266095d"
    # the recorded trace predates the program's spans: nothing to read
    assert ps.reduce(ps.load_events(DATA))["steps"] == 0
