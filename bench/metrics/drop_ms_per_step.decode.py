"""Host time per iteration inside the decode planes' block drops (the
plane.drop spans, counter plane.drop_s), in milliseconds."""


def read(ctx):
    d = ctx["delta"]
    if ctx["iterations"] <= 0 or "plane.drop_s" not in d:
        return None
    return 1000.0 * d["plane.drop_s"] / ctx["iterations"]
