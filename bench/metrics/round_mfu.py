"""Model FLOP utilization of the rounds: the forward and backward FLOPs
of the tau_k local steps each client needs (masked steps do not count)
over the window, the chips and the chip's bf16 peak."""


def read(ctx):
    c, pk = ctx["counters"], ctx["peaks"]
    if not c.get("rounds"):
        return None
    flops = c["tau_sum"] * c["batch"] * c["flops_per_sample_step"]
    chips = ctx["cell"].chips
    return 100.0 * flops / c["window_s"] / chips / pk["bf16_flops_per_s"]
