"""Paged-attention kernel's share of its roofline, in percent: the least
time the chip could take for the attention work the served tokens need
(every decoded token's and every prefill chunk row's queries against
their real contexts, K/V read once per row; bench/work.py, counted from
the configuration's widths and never from the kernel's padded grid)
over the device time of the Pallas paged-attention kernel's events."""

# the instruction name the kernel carries into the device trace
# (`%paged_prefill_gqa.9 = ... custom-call(...)`); decode is its chunk-of-1
KERNEL = "paged_prefill_gqa"


def kernel_ns(red) -> float:
    return sum(e - s for name, s, e, _ in red["op_events"]
               if name.lstrip("%").startswith(KERNEL))


def read(ctx):
    work, cfg = ctx["work"], ctx["cfg"]
    t_kernel = kernel_ns(ctx["trace"]) / 1e9
    if t_kernel <= 0:
        return None
    flops = nbytes = 0.0
    for ctxs in ctx["decode_ctx"]:
        for c in ctxs:
            f, b = work.decode_attn_work(cfg, int(c))
            flops, nbytes = flops + f, nbytes + b
    for past, n in ctx["prefill_rows"]:
        f, b = work.prefill_attn_work(cfg, past, n)
        flops, nbytes = flops + f, nbytes + b
    t_min, _ = work.roofline_s(flops, nbytes, ctx["peak"])
    return 100.0 * t_min / t_kernel
