"""Device time of one decode executable run, from the trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    runs, secs = ctx.trace.module_time(ctx.DECODE)
    return secs / runs * 1e3 if runs else None
