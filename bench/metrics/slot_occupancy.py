"""Mean live slots per decode dispatch in the window: decode-produced
tokens (one per live slot per step) over the loop's decode dispatches."""


def read(ctx):
    return ctx["counters"].get("slot_occupancy")
