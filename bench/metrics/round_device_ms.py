"""Device time of the fused round program per round, from the trace's
XLA module events."""

MODULES = [r"fused"]


def read(ctx):
    t, c = ctx["trace"], ctx["counters"]
    n = t.module_count(MODULES)
    if not n:
        return None
    return 1e3 * t.module_time_s(MODULES) / n
