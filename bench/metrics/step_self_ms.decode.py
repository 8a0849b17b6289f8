"""Host time per iteration inside engine.step that none of its child
spans covers (counter engine.step_self_s), in milliseconds."""


def read(ctx):
    d = ctx["delta"]
    if ctx["iterations"] <= 0 or "engine.step_self_s" not in d:
        return None
    return 1000.0 * d["engine.step_self_s"] / ctx["iterations"]
