"""FlashH2D bytes restored per engine iteration in the window, in MB."""


def read(ctx):
    if ctx["iterations"] <= 0 or "kv.h2d_bytes" not in ctx["delta"]:
        return None
    return ctx["delta"]["kv.h2d_bytes"] / ctx["iterations"] / 1e6
