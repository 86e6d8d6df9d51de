"""Device time of attention (operations under the program's ``attn``
scope: projections, the paged gather, the GQA repeat, scores, softmax and
the KV store) in one decode executable run, from the trace."""


def read(ctx):
    program = getattr(ctx, "program", None)
    if program is None:
        return None
    return program.scope_ms.get("decode", {}).get("attn")
