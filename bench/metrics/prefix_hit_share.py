"""Prompt tokens served from the prefix cache over prompt tokens admitted
in the window (the runtime's ``prefix_tokens_skipped`` counter)."""


def read(ctx):
    if not ctx.window.prompt_tokens_admitted:
        return None
    skipped = ctx.counters["prefix_tokens_skipped"]
    return 100.0 * skipped / ctx.window.prompt_tokens_admitted
