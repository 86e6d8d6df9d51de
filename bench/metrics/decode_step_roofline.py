"""Least time of the decode step's needed work over its device time."""


def read(ctx):
    return ctx.roofline("decode")
