"""Metric arithmetic over the client's records of one window."""

from __future__ import annotations

import dataclasses

import numpy as np


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile with linear interpolation between ranks
    (numpy's default); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


@dataclasses.dataclass
class Window:
    """What the client saw in ``[start, end)``."""

    start: float
    end: float
    attempted: int = 0          # requests due in the window
    failed: int = 0             # of those, refused at submission
    ttft: list = dataclasses.field(default_factory=list)        # s
    gaps: list = dataclasses.field(default_factory=list)        # s
    tokens: int = 0             # output tokens seen in the window
    submit_lag: list = dataclasses.field(default_factory=list)  # s
    queue_wait: list = dataclasses.field(default_factory=list)  # s
    prompt_tokens_admitted: int = 0
    local_frac: float | None = None

    @property
    def seconds(self) -> float:
        return self.end - self.start


def window(records, start: float, end: float) -> Window:
    """TTFT and queue wait are taken from each request's due time over
    every request due in the window; one still waiting at the end counts
    with its wait so far. Gaps and tokens count where the later token
    was seen inside the window."""
    w = Window(start, end)
    lf_sum = lf_n = 0.0
    for r in records:
        t = [x for x in r.token_times if x < end]
        inside = [x for x in t if x >= start]
        w.tokens += len(inside)
        for a, b in zip(t, t[1:]):
            if b >= start:
                w.gaps.append(b - a)
        if start <= r.admitted < end:
            w.prompt_tokens_admitted += r.prompt_len
        if start <= r.finished < end and r.handle is not None:
            lf = r.handle.metrics.get("local_frac")
            if lf is not None:
                lf_sum += lf * len(r.token_times)
                lf_n += len(r.token_times)
        if not start <= r.due < end:
            continue
        w.attempted += 1
        if r.failed:
            w.failed += 1
            continue
        w.submit_lag.append(r.submitted - r.due)
        w.ttft.append((t[0] if t else end) - r.due)
        w.queue_wait.append((r.admitted if r.admitted < end else end) - r.due)
    if lf_n:
        w.local_frac = lf_sum / lf_n
    return w
