"""95th percentile of the gaps between consecutive output tokens."""

from bench.stats import percentile


def read(ctx):
    p = percentile(ctx.window.gaps, 95)
    return None if p is None else p * 1e3
