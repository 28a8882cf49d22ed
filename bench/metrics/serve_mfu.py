"""Model FLOP utilization of serving: the FLOPs of every prompt token
prefilled and every token decoded in the window (matmul weights twice,
windowed attention, the tied LM head where a token needs it) over the
window, the chips and the chip's bf16 peak."""


def read(ctx):
    c, pk = ctx["counters"], ctx["peaks"]
    if not c.get("window_s"):
        return None
    chips = ctx["cell"].chips
    return 100.0 * c["model_flops"] / c["window_s"] / chips \
        / pk["bf16_flops_per_s"]
