"""Least time of the expert FFN's needed work in a decode step (the
architecture's ``moe.expert_ffn`` count) over its device time under the
program's ``moe.expert_ffn`` scope."""


def read(ctx):
    return ctx.scope_roofline("decode", "moe.expert_ffn")
