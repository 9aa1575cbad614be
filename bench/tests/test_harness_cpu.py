"""The whole harness on the CPU at a small size: the timed path, the
trace path and the check, without the command's look for a chip (the
command itself refuses the CPU). Also the check's control and a fault
planted under the timed path, which must both read not correct."""
import os

import numpy as np
import pytest

import harness
import peaks
from smoke import smoke_override


@pytest.fixture(autouse=True)
def _cpu(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    # the CPU has no published peaks; the per-layer readers need some
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def _run(cell, seconds=2.0, trace=False, dtype="float32", **kw):
    _, _, cfg, _, _ = harness.load_cell(cell)
    ov = smoke_override(cfg)
    ov["param_dtype"] = dtype
    return harness.run_cell(cell, 2**33 + 3, seconds, trace, t_process=0.0,
                            cfg_override=ov, **kw)


def test_decode_long_runs_and_checks(tmp_path):
    out = _run("granite-moe-1b-a400m.decode-long", out_dir=str(tmp_path / "tr"))
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "check"
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    for v in out["check"].values():
        assert v["value"] <= v["limit"]


def test_chat_trace_run_reports_per_layer(tmp_path):
    out = _run("granite-moe-1b-a400m.chat-shared", seconds=3.0, trace=True,
               out_dir=str(tmp_path / "tr"))
    assert out["correct"]
    m = out["metrics"]
    # the CPU trace has no TPU device plane: device readers find nothing
    # and stay silent; the counter readers read the program's counters
    assert "device_idle_share" not in m and "attn_kernel_roofline" not in m
    assert m["migrations_per_iter"]["value"] > 0
    assert 0 < m["prefix_hit_share"]["value"] < 100
    assert "ttft_p90_ms" not in m  # --trace 1 lines carry per-layer metrics only


def test_token_altered_where_produced_is_not_correct(tmp_path):
    def sabotage(loop):
        orig = loop.batcher.record
        calls = {"n": 0}

        def record(idx, toks):
            calls["n"] += 1
            toks = np.array(toks, copy=True)
            if calls["n"] % 8 == 0 and len(toks):
                toks[0] = (toks[0] + 1) % loop.cfg.vocab_size
            return orig(idx, toks)

        loop.batcher.record = record

    out = _run("granite-moe-1b-a400m.decode-long", sabotage=sabotage,
               out_dir=str(tmp_path / "tr"))
    assert out["correct"] is False


def test_lower_precision_control_is_not_correct(tmp_path):
    """bf16 at the small size: the float8 control, put in the program's
    place, comes out not correct through the run's own comparison, where
    the program's gaps on the same served sequences pass the same limit."""
    out = _run("granite-moe-1b-a400m.decode-long", dtype="bfloat16", control=True,
               out_dir=str(tmp_path / "tr"))
    assert out["correct"] is False
    assert list(out)[-1] == "check"
    assert all(v["value"] > v["limit"] for v in out["check"].values())
    for k, v in out["check"].items():
        assert out["served"][k] <= v["limit"]
