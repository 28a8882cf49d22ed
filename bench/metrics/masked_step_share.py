"""Share of the masked local-update scan's steps that are masked out:
every client runs tau_max trips, of which only its tau_k count.
1 - sum(tau_k) / (clients * tau_max * rounds), from the window's tau rows."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("rounds"):
        return None
    return 100.0 * (1.0 - c["tau_sum"] / (c["clients"] * c["tau_max"]
                                          * c["rounds"]))
