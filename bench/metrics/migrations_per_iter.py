"""Expert moves the tier planner emitted per loop iteration over the
whole window: the loop's `serving.migrations` counter over iterations."""


def read(ctx):
    if ctx["iters_window"] <= 0:
        return None
    return ctx["counters_window"]["serving.migrations"] / ctx["iters_window"]
