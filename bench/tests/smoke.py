"""Small-size overrides for running a cell's whole harness on the CPU."""


def smoke_override(cfg: dict) -> dict:
    """Same architecture, tiny widths: the CPU rehearsal size."""
    return {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "head_dim": 16,
        "d_ff": 128 if cfg["d_ff"] else 0, "vocab_size": 256,
        "moe": {**cfg["moe"], "n_experts": 8, "top_k": 2, "d_expert": 32,
                "n_shared": min(1, cfg["moe"]["n_shared"])},
        "deployment": {**cfg["deployment"], "slots": 4},
        "param_dtype": "float32",
    }
