"""The glm-4.5-air-stage.decode-long cell on the CPU at a small size: the
whole harness (timed path, trace path and check) through the
`glm4_moe` architecture module, its control, and the expert-FFN reader
on a reduced trace written by hand."""
import numpy as np
import pytest

import harness
import peaks
import trace_reduce as tr
from smoke import smoke_override
from trace_reduce import Ev

CELL = "glm-4.5-air-stage.decode-long"


@pytest.fixture(autouse=True)
def _cpu(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    # the CPU has no published peaks; the per-layer readers need some
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def _run(seconds=2.0, trace=False, dtype="float32", **kw):
    _, _, cfg, _, _ = harness.load_cell(CELL)
    ov = smoke_override(cfg)
    ov["param_dtype"] = dtype
    return harness.run_cell(CELL, 2**33 + 5, seconds, trace, t_process=0.0,
                            cfg_override=ov, **kw)


def test_small_stage_runs_and_checks(tmp_path):
    """The smoke size keeps the architecture: a dense layer then an MoE
    layer whose router has all 128 outputs, of which 8 experts are held."""
    out = _run(trace=True, out_dir=str(tmp_path / "tr"))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    for v in out["check"].values():
        assert v["value"] <= v["limit"]
    m = out["metrics"]
    assert m["migrations_per_iter"]["value"] >= 0
    # the CPU trace has no TPU device plane: device readers stay silent
    assert "expert_ffn_ms_per_iter" not in m and "device_idle_share" not in m


def test_lower_precision_control_is_not_correct(tmp_path):
    out = _run(dtype="bfloat16", control=True, out_dir=str(tmp_path / "tr"))
    assert out["correct"] is False
    assert all(v["value"] > v["limit"] for v in out["check"].values())
    for k, v in out["check"].items():
        assert out["served"][k] <= v["limit"]


def test_expert_ffn_reader_on_a_reduced_trace():
    """Both expert-FFN kernels' events in the window, per traced
    iteration; other ops do not count. An event that overlaps the
    window counts whole, as the attention reader counts its kernel's."""
    host = [Ev("main", "bench.window", 0, 100, {}),
            Ev("main", "bench.step", 0, 50, {}),
            Ev("main", "bench.step", 50, 100, {})]
    dev = [
        Ev(tr.MODULES_LINE, "jit_step_paged(3)", 0, 45, {}),
        Ev(tr.OPS_LINE, "%expert_gemv.3 = bf16[32,128,4096]{2,1,0} custom-call()", 5, 15, {}),
        Ev(tr.OPS_LINE, "%paged_prefill_gqa.9 = bf16[1]{0} custom-call()", 15, 40, {}),
        Ev(tr.MODULES_LINE, "jit_prefill_paged_fn(4)", 50, 95, {}),
        Ev(tr.OPS_LINE, "%moe_gemm.1 = bf16[1]{0} custom-call()", 55, 61, {}),
        Ev(tr.OPS_LINE, "%moe_gemm.2 = bf16[1]{0} custom-call()", 61, 65, {}),
        Ev(tr.OPS_LINE, "%expert_gemv.3 = bf16[1]{0} custom-call()", 98, 120, {}),
        Ev(tr.OPS_LINE, "%expert_gemv.3 = bf16[1]{0} custom-call()", 130, 140, {}),
    ]
    red = tr.reduce({"device": dev, "host": host})
    mod = harness.load_module("metrics", "expert_ffn_ms_per_iter")
    # 10 + 6 + 4 + 22 ns over 2 iterations
    assert mod.read({"trace": red}) == pytest.approx(42 / 1e6 / 2)
    red["op_events"] = [e for e in red["op_events"] if "paged" in e[0]]
    assert mod.read({"trace": red}) is None


def test_program_weights_route_over_all_experts_and_hold_a_share():
    """The program's weights carry the router at its published width
    and the tables one entry per router output, the experts held
    elsewhere in no tier."""
    _, _, cfg, _, _ = harness.load_cell(CELL)
    cfg = {**cfg, **smoke_override(cfg)}
    arch = harness.load_module("references", cfg["reference"])
    from repro.serving.tiered_moe import AWAY_T, tier_sizes

    mcfg = arch.model_config(cfg)
    sizes = tier_sizes(mcfg)
    params, tiered = arch.program_weights(7, cfg, mcfg, sizes)
    ffn = params["stack"]["slot1"]["ffn"]
    assert ffn["router"].shape[-1] == ffn["router_bias"].shape[-1] == 128
    tier = np.asarray(tiered["stack"]["slot1"]["expert_tier"])
    assert tier.shape[-1] == 128
    held = cfg["moe"]["n_experts"]
    assert (tier[..., held:] == AWAY_T).all() and (tier[..., :held] < AWAY_T).all()
