"""Operation and byte counts, checked by hand at a small configuration."""
import pytest

import peaks
import work

CFG = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2, "head_dim": 2,
       "d_ff": 16, "vocab_size": 10,
       "moe": {"n_experts": 4, "top_k": 2, "d_expert": 3, "n_shared": 1,
               "layer_pattern": "all_but_first"}}


def test_matmul_flops_by_hand():
    # attention per layer: q 8x(4*2) + k,v 8x(2*2) each + o (4*2)x8
    attn = 2 * (8 * 8 + 2 * 8 * 4) + 2 * 8 * 8  # 256
    dense = 2 * 3 * 8 * 16  # 768, layer 0
    moe = 2 * 8 * 4 + (2 + 1) * 2 * 3 * 8 * 3  # router 64 + 3 experts 432
    head = 2 * 8 * 10
    assert work.matmul_flops_per_token(CFG) == 2 * attn + dense + moe + head


def test_attention_counts_by_hand():
    # one query over 5 keys, one layer: QK and PV, 2*5*hd each, 4 heads
    assert work.attn_flops(CFG, 5) == 4 * 5 * 4 * 2
    # K and V of 5 keys, 2 kv heads, hd 2, bf16
    assert work.attn_kv_bytes(CFG, 5) == 2 * 5 * 2 * 2 * 2
    f, b = work.decode_attn_work(CFG, 5)
    assert (f, b) == (2 * 160, 2 * 80)
    # a chunk of 3 after 4 cached tokens: queries see 5, 6, 7 keys
    f, b = work.prefill_attn_work(CFG, 4, 3)
    assert f == 2 * 4 * (5 + 6 + 7) * 4 * 2
    assert b == 2 * work.attn_kv_bytes(CFG, 7)


def test_roofline_picks_the_binding_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = work.roofline_s(197e12, 1.0, p)
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = work.roofline_s(1.0, 819e9, p)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
