"""The vecavg server-reduce kernel's share of its roofline: the bytes one
reduce needs ([C, D] f32 read, [D] written) over its device time, against
the chip's peak HBM bandwidth (compute is far below its peak)."""

OPS = [r"vecavg"]


def read(ctx):
    t, c, w, pk = ctx["trace"], ctx["counters"], ctx["work"], ctx["peaks"]
    calls = t.op_count(OPS)
    secs = t.op_time_s(OPS)
    if not calls or secs <= 0:
        return None
    C, D = c["clients"], c["n_params"]
    least = w.roofline_s(w.vecavg_flops(C, D), w.vecavg_bytes(C, D), pk)
    return 100.0 * calls * least / secs
