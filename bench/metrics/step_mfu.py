"""Whole step's share of the chip's bf16 peak, in percent: the FLOPs
the tokens served in the traced window need (decoded tokens at their
contexts, prefilled prompt tokens that missed the prefix cache; weight
matmuls with only the routed top-k and the shared experts, plus
attention; bench/work.py) over traced window x peak FLOP/s."""


def read(ctx):
    work, cfg = ctx["work"], ctx["cfg"]
    red = ctx["trace"]
    per_tok = work.matmul_flops_per_token(cfg)
    flops = 0.0
    for ctxs in ctx["decode_ctx"]:
        flops += len(ctxs) * per_tok
        flops += sum(cfg["n_layers"] * work.attn_flops(cfg, c) for c in ctxs)
    for past, n in ctx["prefill_rows"]:
        flops += n * per_tok + work.prefill_attn_work(cfg, past, n)[0]
    if flops <= 0 or red["window_ns"] <= 0:
        return None
    return 100.0 * flops / (red["window_ns"] / 1e9 * ctx["peak"]["bf16_flops"])
