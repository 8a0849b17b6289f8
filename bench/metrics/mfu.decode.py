"""The decode step's share of the chip's peak: the model's operations
for every output token of the window (at its own context length) per
window second, over the peak of bench/peaks.json."""


def read(ctx):
    if ctx["decode_flops"] <= 0:
        return None
    return 100.0 * ctx["decode_flops"] / ctx["window_s"] / \
        ctx["peak"]["flops"]
