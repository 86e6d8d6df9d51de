"""Median over the traced ``serve.tick`` spans of each tick's time less
the ``serve.wait`` inside it: the host's own work in a tick."""

import numpy as np


def read(ctx):
    program = getattr(ctx, "program", None)
    if program is None or not program.tick_self_ms:
        return None
    return float(np.median(program.tick_self_ms))
