"""Seconds from process start to the window's start: imports, weights,
engine, compiles or cache loads, and the set-up the mix needs."""


def read(ctx):
    return ctx["setup_s"]
