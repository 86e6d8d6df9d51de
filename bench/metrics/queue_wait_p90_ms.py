"""90th percentile of due time to admission seen on the host."""

from bench.stats import percentile


def read(ctx):
    p = percentile(ctx.window.queue_wait, 90)
    return None if p is None else p * 1e3
