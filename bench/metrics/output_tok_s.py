"""Output tokens that came back inside the window, per window second."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return ctx["output_tokens"] / ctx["window_s"]
