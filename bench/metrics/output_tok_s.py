"""Output tokens seen in the window over the window's seconds."""


def read(ctx):
    return ctx.window.tokens / ctx.window.seconds
