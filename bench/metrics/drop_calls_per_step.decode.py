"""Block drops per iteration: the decode planes' drop_blocks calls
(the plane.drop spans, counter plane.drop_calls)."""


def read(ctx):
    d = ctx["delta"]
    if ctx["iterations"] <= 0 or "plane.drop_calls" not in d:
        return None
    return d["plane.drop_calls"] / ctx["iterations"]
