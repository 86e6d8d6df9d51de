"""Model FLOPs the decode steps needed over their device time at peak."""


def read(ctx):
    return ctx.mfu("decode")
