"""Host time per iteration dispatching the new-token KV stripe gathers
(the plane.kv_readback spans, counter plane.kv_readback_s), in
milliseconds."""


def read(ctx):
    d = ctx["delta"]
    if ctx["iterations"] <= 0 or "plane.kv_readback_s" not in d:
        return None
    return 1000.0 * d["plane.kv_readback_s"] / ctx["iterations"]
