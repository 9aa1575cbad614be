"""Device time of the paged-prefill program per prefill chunk call, in
ms: op time inside `jit_prefill_paged_fn` executions over the loop's
`serving.prefill_chunks` counter for the traced span."""

MODULE = "jit_prefill_paged_fn"


def read(ctx):
    m = ctx["trace"]["modules"].get(MODULE)
    chunks = ctx["counters_trace"]["serving.prefill_chunks"]
    if not m or chunks <= 0 or m["busy_ns"] <= 0:
        return None
    return m["busy_ns"] / 1e6 / chunks
