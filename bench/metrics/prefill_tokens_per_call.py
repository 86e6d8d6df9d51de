"""Valid prompt tokens per chunked-prefill call over the window: the
runtime's ``prefill_tokens`` over its ``prefill_calls``."""


def read(ctx):
    program = getattr(ctx, "program", None)
    if program is None:
        return None
    tokens = program.counters.get("prefill_tokens")
    calls = program.counters.get("prefill_calls")
    return tokens / calls if tokens is not None and calls else None
