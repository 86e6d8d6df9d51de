"""90th percentile of admission to first token, from the handles' host
clock stamps, over the requests admitted in the window; one still
prefilling at the end counts with its wait so far."""

from bench.stats import percentile


def read(ctx):
    program = getattr(ctx, "program", None)
    if program is None or not program.prefill_waits:
        return None
    return percentile(program.prefill_waits, 90) * 1e3
