"""Share of the traced window in which no operation ran on the device
(paged serving)."""


def read(ctx):
    t = ctx["trace"]
    if not t.n_devices:
        return None
    return 100.0 * t.idle_share()
