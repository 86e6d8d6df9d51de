"""Least time of the prefill chunk's needed work over its device time."""


def read(ctx):
    return ctx.roofline("chunk")
