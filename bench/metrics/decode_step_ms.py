"""Device time per decode dispatch: the decode program's summed device
time over its count in the trace."""

MODULES = [r"_decode\b|_decode\("]


def read(ctx):
    t = ctx["trace"]
    n = t.module_count(MODULES)
    if not n:
        return None
    return 1e3 * t.module_time_s(MODULES) / n
