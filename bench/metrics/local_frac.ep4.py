"""Share of (token, expert) activations served on the request's origin
chip: the runtime's per-request decode locality, weighted by tokens, over
the requests that finished in the window."""


def read(ctx):
    lf = ctx.window.local_frac
    return None if lf is None or ctx.chips < 2 else 100.0 * lf
