"""What a per-layer metric's reader is given: the client's window, the
runtime's counters over it, the reduced trace and the needed work of the
steps launched while it was traced."""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import flops


@dataclasses.dataclass
class Context:
    window: object              # stats.Window
    counters: dict              # runtime counters, window end minus start
    chips: int
    peaks: dict
    trace: object = None        # reduce_trace.Summary (traced runs only)
    work: dict = None           # kind -> list of per-call flops.* dicts

    # executable names of the program's serving steps (its jitted
    # functions ``_dec`` and ``_chunk``, run as ``jit__dec``/``jit__chunk``)
    DECODE = r"jit__dec\b"
    CHUNK = r"jit__chunk\b"

    def _step(self, kind: str):
        if self.trace is None or not self.work or not self.work.get(kind):
            return None
        runs, secs = self.trace.module_time(
            self.DECODE if kind == "decode" else self.CHUNK)
        if not runs or not secs:
            return None
        calls = self.work[kind]
        mean = {k: float(np.mean([c[k] for c in calls]))
                for k in ("flops", "bytes")}
        return mean, secs / runs

    def roofline(self, kind: str):
        """{"value": %, "bound": "flops"|"hbm"} or None."""
        got = self._step(kind)
        if got is None:
            return None
        mean, per_run = got
        r = flops.roofline(mean, per_run, self.peaks, self.chips)
        return {"value": 100.0 * r["share"], "bound": r["bound"]}

    def mfu(self, kind: str):
        got = self._step(kind)
        if got is None:
            return None
        mean, per_run = got
        return 100.0 * mean["flops"] / (per_run * self.peaks["bf16_flops"]
                                         * self.chips)
