"""Prompt tokens prefilled inside the window, per window second: each
request's progress at the window's end minus at its start."""


def read(ctx):
    if ctx["window_s"] <= 0 or ctx["prompt_tokens"] <= 0:
        return None
    return ctx["prompt_tokens"] / ctx["window_s"]
