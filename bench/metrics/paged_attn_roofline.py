"""The paged decode kernel's share of its roofline: the bytes and FLOPs
the LIVE context needs (K/V rows within each live slot's position and
window, the new row, q and o; bench/work.py), summed over the window's
decode steps, against the kernel's device time and the chip's peaks. How
an implementation walks pages does not raise the count."""

OPS = [r"_decode_kernel|paged_decode_attention"]


def read(ctx):
    t, c, w, pk = ctx["trace"], ctx["counters"], ctx["work"], ctx["peaks"]
    secs = t.op_time_s(OPS)
    if secs <= 0 or not c.get("attn_bytes"):
        return None
    return 100.0 * w.roofline_s(c["attn_flops"], c["attn_bytes"], pk) / secs
