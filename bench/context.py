"""What a per-layer metric's reader is given: the client's window, the
runtime's counters over it, the reduced trace, the program's own spans,
scopes, stamps and counters read over it, and the needed work of the steps
launched while it was traced."""

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
    work: dict = None           # "decode"/"chunk" -> per call, the arch's
    #                             work: {"step" or scope: {flops, bytes}}
    program: object = None      # program_spans.Reading (traced runs only)

    # executable names of the program's serving steps (its jitted
    # functions ``_dec`` and ``_chunk``, run as ``jit__dec``/``jit__chunk``)
    DECODE = r"jit__dec\b"
    CHUNK = r"jit__chunk\b"

    def _mean_work(self, kind: str, part: str):
        calls = [c[part] for c in (self.work or {}).get(kind, ())
                 if part in c]
        if not calls:
            return None
        return {k: float(np.mean([c[k] for c in calls]))
                for k in ("flops", "bytes")}

    def _step(self, kind: str):
        """(mean needed work of a call, device seconds per run)."""
        mean = self._mean_work(kind, "step")
        if self.trace is None or mean is None:
            return None
        runs, secs = self.trace.module_time(
            self.DECODE if kind == "decode" else self.CHUNK)
        if not runs or not secs:
            return None
        return mean, secs / runs

    def _share(self, mean: dict, seconds: float) -> dict:
        r = flops.roofline(mean, seconds, self.peaks, self.chips)
        return {"value": 100.0 * r["share"], "bound": r["bound"]}

    def roofline(self, kind: str):
        """{"value": %, "bound": "flops"|"hbm"} of the whole step, or None."""
        got = self._step(kind)
        return None if got is None else self._share(*got)

    def scope_roofline(self, kind: str, scope: str):
        """The same over one program scope's needed work and device time
        per run of the step (``program.scope_ms``), or None."""
        mean = self._mean_work(kind, scope)
        ms = (None if self.program is None
              else self.program.scope_ms.get(kind, {}).get(scope))
        if mean is None or not ms:
            return None
        return self._share(mean, ms * 1e-3)

    def mfu(self, kind: str):
        got = self._step(kind)
        if got is None:
            return None
        mean, per_run = got
        return 100.0 * mean["flops"] / (per_run * self.peaks["bf16_flops"]
                                         * self.chips)
