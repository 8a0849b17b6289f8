"""The prefill's share of the chip's peak: the model's operations for the
prompt work done inside the window (dense and causal attention, from each
request's prefill cursor) per window second, over the peak."""


def read(ctx):
    if ctx["prefill_flops"] <= 0:
        return None
    return 100.0 * ctx["prefill_flops"] / ctx["window_s"] / \
        ctx["peak"]["flops"]
