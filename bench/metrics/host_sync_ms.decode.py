"""Host time per iteration the dispatch thread spent blocked on the
selected block ids (plane.dispatch_sync_s), in milliseconds."""


def read(ctx):
    d = ctx["delta"]
    if ctx["iterations"] <= 0 or "plane.dispatch_sync_s" not in d:
        return None
    return 1000.0 * d["plane.dispatch_sync_s"] / ctx["iterations"]
