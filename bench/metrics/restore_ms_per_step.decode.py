"""Host time per iteration landing FlashH2D payloads in the decode
planes' slots (the plane.restore spans, counter plane.restore_s), in
milliseconds."""


def read(ctx):
    d = ctx["delta"]
    if ctx["iterations"] <= 0 or "plane.restore_s" not in d:
        return None
    return 1000.0 * d["plane.restore_s"] / ctx["iterations"]
