"""Model FLOPs the prefill chunks needed over their device time at peak."""


def read(ctx):
    return ctx.mfu("chunk")
