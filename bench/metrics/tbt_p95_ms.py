"""95th percentile of every gap between consecutive output tokens of one
request that ends inside the window, in milliseconds."""
from benchkit.timeline import percentile


def read(ctx):
    p = percentile(ctx["gaps"], 95)
    return None if p is None else 1000.0 * p
