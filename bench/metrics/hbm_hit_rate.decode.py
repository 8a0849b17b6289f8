"""Share of the window's DSA-selected (layer, block) accesses that found
the block resident in the per-request HBM LRU (kv.hits / accesses)."""


def read(ctx):
    d = ctx["delta"]
    n = d.get("kv.hits", 0.0) + d.get("kv.misses", 0.0)
    if n <= 0:
        return None
    return 100.0 * d.get("kv.hits", 0.0) / n
