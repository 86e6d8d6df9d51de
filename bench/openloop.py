"""The open-loop client: submits each request at its due time, between
calls to the system's ``step()``, and timestamps what it sees on the host.

All times are ``time.perf_counter()`` seconds. A token is stamped when the
client first sees it after a ``step()`` returns; under the zero-stall loop
that is one round after its launch, when a server could first stream it.
The client's own work is wrapped in ``jax.profiler.TraceAnnotation`` spans
(``bench.submit``, ``bench.step``, ``bench.record``, ``bench.idle``) so a
device trace can name what the host was doing in each idle gap.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Record:
    """What the client saw of one request."""

    due: float
    prompt_len: int
    max_new_tokens: int
    origin: int | None
    submitted: float = float("nan")
    admitted: float = float("nan")
    token_times: list = dataclasses.field(default_factory=list)
    finished: float = float("nan")
    handle: object = None
    failed: bool = False


def drive(system, requests, t0: float, t_end: float, marks=(), annotate=None):
    """Serve ``requests`` (``generator.Timed``, due at ``t0 + at``) until
    ``t_end``. ``marks``: ``(time, callback)`` pairs called once the clock
    passes ``time`` (window start and end). Returns one ``Record`` per
    request submitted, in due order."""
    from repro.serving.api import Request

    if annotate is None:
        import jax

        annotate = jax.profiler.TraceAnnotation
    marks = sorted(marks, key=lambda m: m[0])
    records: list[Record] = []
    live: list[Record] = []
    i, n = 0, len(requests)
    clock = time.perf_counter
    while True:
        now = clock()
        while marks and now >= marks[0][0]:
            marks.pop(0)[1]()
            now = clock()
        if now >= t_end:
            break
        if i < n and t0 + requests[i].at <= now:
            with annotate("bench.submit"):
                while i < n and t0 + requests[i].at <= now:
                    q = requests[i]
                    rec = Record(due=t0 + q.at, prompt_len=len(q.prompt),
                                 max_new_tokens=q.max_new_tokens,
                                 origin=q.origin)
                    try:
                        rec.handle = system.submit(Request(
                            prompt=q.prompt, max_new_tokens=q.max_new_tokens,
                            origin=q.origin))
                        live.append(rec)
                    except ValueError:
                        rec.failed = True
                    rec.submitted = clock()
                    records.append(rec)
                    i += 1
        if system.busy():
            with annotate("bench.step"):
                system.step()
        else:
            nxt = t0 + requests[i].at if i < n else t_end
            if marks:
                nxt = min(nxt, marks[0][0])
            with annotate("bench.idle"):
                time.sleep(max(0.0, min(nxt, t_end) - clock()))
            continue
        with annotate("bench.record"):
            now = clock()
            keep = []
            for rec in live:
                h = rec.handle
                if rec.admitted != rec.admitted and h.admitted_at is not None:
                    rec.admitted = now
                new = len(h.tokens) - len(rec.token_times)
                if new > 0:
                    rec.token_times.extend([now] * new)
                if h.done:
                    rec.finished = now
                else:
                    keep.append(rec)
            live = keep
    return records


def tokens_of(rec: Record) -> np.ndarray:
    """The tokens the system served for one request."""
    return np.asarray(rec.handle.tokens, np.int32)
