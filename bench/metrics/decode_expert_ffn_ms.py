"""Device time of the expert FFN (operations under the program's
``moe.expert_ffn`` scope) in one decode executable run, from the trace."""


def read(ctx):
    program = getattr(ctx, "program", None)
    if program is None:
        return None
    return program.scope_ms.get("decode", {}).get("moe.expert_ffn")
