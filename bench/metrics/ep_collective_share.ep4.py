"""Device time of collectives inside the decode and prefill executables
over those executables' device time."""


def read(ctx):
    if ctx.trace is None or ctx.chips < 2:
        return None
    pattern = f"{ctx.DECODE}|{ctx.CHUNK}"
    _, secs = ctx.trace.module_time(pattern)
    if not secs:
        return None
    return 100.0 * ctx.trace.collective_time(pattern) / secs
