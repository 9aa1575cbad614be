"""Share of admitted prompt tokens served from the radix prefix cache
over the whole window, in percent: the KV cache's `hit_tokens` over its
`lookup_tokens` (prompt tokens looked up at admission)."""


def read(ctx):
    c = ctx["counters_window"]
    if c["kv.lookup_tokens"] <= 0:
        return None
    return 100.0 * c["kv.hit_tokens"] / c["kv.lookup_tokens"]
