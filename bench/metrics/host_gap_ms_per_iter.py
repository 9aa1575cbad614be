"""Device-idle time inside the loop's iterations: the part of each
`ServingLoop.step_once` call (the harness's `bench.step` annotation) in
which no operation ran on the device, summed and divided by the
iterations traced, in ms. Source: the device trace and the harness's
host annotations on the profiler's clock."""


def read(ctx):
    red = ctx["trace"]
    if red["steps"] <= 0:
        return None
    return red["idle_in_steps_ns"] / 1e6 / red["steps"]
