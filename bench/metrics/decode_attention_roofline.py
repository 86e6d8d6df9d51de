"""Least time of attention's needed work in a decode step (the
architecture's ``attn`` count) over its device time under the program's
``attn`` scope."""


def read(ctx):
    return ctx.scope_roofline("decode", "attn")
