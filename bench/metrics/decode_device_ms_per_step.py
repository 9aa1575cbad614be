"""Device time of the decode-step program per call, in ms: op time
inside the `jit_step_paged` module's executions over their count.
Source: the device trace."""

MODULE = "jit_step_paged"


def read(ctx):
    m = ctx["trace"]["modules"].get(MODULE)
    if not m or m["n"] == 0 or m["busy_ns"] <= 0:
        return None
    return m["busy_ns"] / 1e6 / m["n"]
