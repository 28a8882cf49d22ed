"""Host seconds inside the round driver's dispatch calls per round
(``TrainDriver.dispatch_s`` over the window's rounds)."""

SOURCE = "TrainDriver.dispatch_s"


def read(ctx):
    c = ctx["counters"]
    if not c.get("rounds"):
        return None
    return 1e3 * c["dispatch_s"] / c["rounds"]
