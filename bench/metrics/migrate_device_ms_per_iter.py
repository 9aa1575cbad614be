"""Device time of the migration swaps per loop iteration, in ms: op time
inside the `jit_migrate_stack` and `jit_apply_migrations` modules over
the iterations traced. Source: the device trace."""

MODULES = ("jit_migrate_stack", "jit_apply_migrations")


def read(ctx):
    red = ctx["trace"]
    ns = sum(red["modules"][m]["busy_ns"] for m in MODULES if m in red["modules"])
    if red["steps"] <= 0 or ns <= 0:
        return None
    return ns / 1e6 / red["steps"]
