"""99th percentile of how late the client submitted each request."""

from bench.stats import percentile


def read(ctx):
    p = percentile(ctx.window.submit_lag, 99)
    return None if p is None else p * 1e3
