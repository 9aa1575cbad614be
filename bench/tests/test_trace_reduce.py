"""The trace reduction, on events written by hand and on a small trace
recorded on a TPU v5e (bench/tests/data/small_trace.xplane.pb, written by
bench/tests/record_small_trace.py; its source paths were shortened)."""
import os

import pytest

import trace_reduce as tr
from trace_reduce import Ev

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.xplane.pb")


def _events():
    host = [
        Ev("main", "bench.window", 0, 100, {}),
        Ev("main", "bench.step", 0, 60, {}),
        Ev("main", "bench.decode_call", 5, 20, {}),
        Ev("main", "bench.migrate_call", 40, 55, {}),
        Ev("main", "bench.submit", 60, 100, {}),
    ]
    dev = [
        Ev(tr.MODULES_LINE, "jit_step_paged(7)", 10, 40, {}),
        Ev(tr.OPS_LINE, "fusion.1", 10, 20, {}),
        Ev(tr.OPS_LINE, "custom-call.3", 18, 30, {}),  # overlaps fusion.1
        Ev(tr.MODULES_LINE, "jit_migrate_stack(9)", 50, 70, {}),
        Ev(tr.OPS_LINE, "copy.2", 50, 70, {}),
        Ev(tr.OPS_LINE, "fusion.1", 95, 110, {}),  # clipped at the window end
    ]
    return {"device": dev, "host": host}


def test_busy_union_and_window():
    red = tr.reduce(_events())
    assert red["window_ns"] == 100
    # [10,30] + [50,70] + [95,100] = 20 + 20 + 5
    assert red["busy_ns"] == 45
    assert red["op_time_ns"] == {"jit_step_paged:fusion.1": 10, "?:fusion.1": 5,
                                 "jit_step_paged:custom-call.3": 12,
                                 "jit_migrate_stack:copy.2": 20}


def test_modules_and_layers():
    red = tr.reduce(_events())
    assert red["modules"]["jit_step_paged"]["n"] == 1
    assert red["modules"]["jit_step_paged"]["busy_ns"] == 20  # ops inside [10,40]
    assert red["modules"]["jit_migrate_stack"]["busy_ns"] == 20
    table = tr.module_table(red)
    assert list(table)[0] in ("jit_step_paged", "jit_migrate_stack")
    assert table["jit_step_paged"]["layer"].startswith("engine")
    assert table["jit_migrate_stack"]["layer"].startswith("tiers")
    assert tr.layer_of("jit_something_new(3)") == "other"


def test_gaps_named_by_host_activity_and_inside_steps():
    red = tr.reduce(_events())
    gaps = [(s, e) for s, e, _ in red["gaps"]]
    assert gaps == [(0, 10), (30, 50), (70, 95)]
    names = [w for _, _, w in red["gaps"]]
    assert names[0] == "bench.decode_call"  # innermost over [0,10] with most overlap
    assert names[1] == "bench.migrate_call"
    assert names[2] == "bench.submit"
    # idle inside the one step [0,60]: [0,10] + [30,50]
    assert red["idle_in_steps_ns"] == 30
    assert red["steps"] == 1


def test_breakdown_is_sorted_and_capped():
    red = tr.reduce(_events())
    b = tr.breakdown(red, top=2)
    assert [n for n, _ in b["device_ops"]] == ["jit_migrate_stack:copy.2",
                                               "jit_step_paged:custom-call.3"]
    assert b["device_ops"][0][1] == pytest.approx(20e-9)
    idle = b["idle_gaps"]
    assert len(idle) == 2 and idle[0][1] >= idle[1][1]
    assert idle[0][0] == "bench.submit"  # 25 ns, the longest


def test_op_label_from_hlo_text():
    text = ("%copy.67 = bf16[24,16,3,1024,512]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[24,16,3,1024,512]{4,3,2,1,0} %get-tuple-element.9)")
    assert tr.op_label(text) == "copy.67 bf16[24,16,3,1024,512]"
    assert tr.op_label("%while.104 = (s32[]{:T(128)}, bf16[64]) while(...)") == "while.104"
    assert tr.op_label("fusion.1") == "fusion.1"


def test_union_merges_touching_and_nested():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_tpu_trace():
    ev = tr.load_events(DATA)
    assert any(h.name == "bench.window" for h in ev["host"])
    red = tr.reduce(ev)
    assert 0 < red["busy_ns"] <= red["window_ns"]
    assert red["steps"] == 3
    # the Pallas paged-attention kernel shows under the name its
    # pallas_call sets, which the roofline reader keys on
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "attn", os.path.join(tr.BENCH_DIR, "metrics", "attn_kernel_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.kernel_ns(red) > 0
