"""Median time to first token over the requests outstanding in the
window; one still waiting at its end counts until the end."""
from benchkit.timeline import percentile


def read(ctx):
    return percentile(ctx["ttfts"], 50)
