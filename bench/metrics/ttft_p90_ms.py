"""90th percentile of time to first token, from each request's due time."""

from bench.stats import percentile


def read(ctx):
    p = percentile(ctx.window.ttft, 90)
    return None if p is None else p * 1e3
