"""Seeded violation: a tracer span opened inside a jitted stage body.

The obs layer is host-side — a ``tracer.span(...)`` here runs once while
jax traces the function and never again, so the span silently vanishes
from every subsequent launch (and a counter would undercount by
iterations-1).  Instrumentation belongs in the DRIVER, around the stage
launch (see ``DevicePoolPlane.step_staged`` and ``StageFns.wrap``).
"""


def build(wrap, tracer):
    def attend(p, x):
        with tracer.span("stage.attend"):
            h = x @ p["w"]
        return h

    return wrap("attend", attend)
