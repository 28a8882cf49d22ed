"""Share of device busy time spent in the prefill and page-insert
programs."""

MODULES = [r"_prefill_step", r"insert_cache_pages"]


def read(ctx):
    t = ctx["trace"]
    busy = t.busy_s()
    if busy <= 0 or not t.module_count(MODULES):
        return None
    return 100.0 * t.module_time_s(MODULES) / busy
