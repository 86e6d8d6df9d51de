"""Reduction of a profiler trace to device time, idle share and what the
host was doing in each idle gap.

A trace is read into plain events ``(plane, line, name, start_ns, dur_ns)``
from the ``.xplane.pb`` the JAX profiler writes (``load_xplane``), or from
the small JSON form the tests keep (``load_json``). Device planes are the
``/device:TPU:<n>`` planes; on each, the ``XLA Ops`` line holds the
operations and the ``XLA Modules`` line one event per executable run.
Host spans are the benchmark's ``bench.*`` annotations.
"""

from __future__ import annotations

import dataclasses
import functools
import gzip
import json
import re
from pathlib import Path

import numpy as np

DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute",
    re.IGNORECASE)
HOST_SPAN = re.compile(r"^bench\.")
# control flow whose time is that of the operations inside it
CONTAINERS = {"while", "conditional", "call"}


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: int   # ns
    dur: int     # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


def load_xplane(path) -> list[Event]:
    """The events of an ``.xplane.pb`` file that the reduction reads: the
    device planes' op and executable lines, and the host's ``bench.*``
    spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        device = bool(DEVICE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if device or HOST_SPAN.match(name):
                    out.append(Event(plane.name, line.name, name,
                                     int(ev.start_ns), int(ev.duration_ns)))
    return out


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_json(path) -> list[Event]:
    """The tests' form: ``{"events": [[plane, line, name, start, dur], ...]}``
    (gzip when the name ends in ``.gz``)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        return [Event(*e) for e in json.load(f)["events"]]


def dump_json(events, path) -> None:
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump({"events": [dataclasses.astuple(e) for e in events]}, f)


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


@dataclasses.dataclass
class Summary:
    """What a trace says about the device over one window."""

    window_s: float
    busy_s: float                      # union of op intervals, mean of chips
    devices: int
    modules: dict                      # executable -> [runs, seconds]
    collective_s: dict                 # executable -> collective op seconds
    top_ops: list                      # [(op, seconds)], most time first
    idle_gaps: list                    # [(host span, seconds)], longest first

    def module_time(self, pattern: str) -> tuple[int, float]:
        """Runs and device seconds (mean over chips) of the executables
        whose name matches ``pattern``."""
        rx = re.compile(pattern)
        runs = sum(v[0] for k, v in self.modules.items() if rx.search(k))
        secs = sum(v[1] for k, v in self.modules.items() if rx.search(k))
        return runs, secs

    def collective_time(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.collective_s.items() if rx.search(k))


@functools.lru_cache(maxsize=None)
def op_label(name: str) -> tuple[str, str]:
    """(short label, opcode) of an op event named by its HLO text, e.g.
    ``%fusion.313 = bf16[8,1288,14336]{...} fusion(...)`` ->
    (``%fusion.313 bf16[8,1288,14336] fusion``, ``fusion``)."""
    m = re.match(r"^(%?[\w.\-]+) = (.*)$", name)
    if not m:
        return name[:120], name
    inst, rest = m.groups()
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "(tuple)", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = re.sub(r"\{[^}]*\}", "", shape)
    op = re.match(r"[\w\-]+", rest)
    op = op.group(0) if op else ""
    return f"{inst} {shape} {op}"[:120], op


def _module_key(name: str) -> str:
    """An executable's name without the run id some traces append."""
    return re.sub(r"\(\d+\)$", "", name)


def summarize(events, window=None, n_gaps: int = 10, n_ops: int = 10) -> Summary:
    """Reduce ``events`` over ``window`` (ns pair; default: from the first
    to the last device event)."""
    dev = [e for e in events if DEVICE.match(e.plane)]
    planes = sorted({e.plane for e in dev})
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    if window is None:
        window = (min(e.start for e in dev), max(e.end for e in dev))
    lo, hi = window
    n = len(planes)
    busy = 0
    modules: dict[str, list] = {}
    collective: dict[str, float] = {}
    ops: dict[str, float] = {}
    first_busy = None
    for p in planes:
        op_ev = [e for e in dev if e.plane == p and e.line == OPS_LINE]
        mod_ev = [e for e in dev if e.plane == p and e.line == MODULES_LINE]
        spans = _clip(_union((e.start, e.end) for e in (op_ev or mod_ev)),
                      lo, hi)
        busy += sum(e - s for s, e in spans)
        if first_busy is None:
            first_busy = spans
        mods = sorted((e for e in mod_ev if lo <= e.start and e.end <= hi),
                      key=lambda e: e.start)
        for m in mods:
            k = _module_key(m.name)
            rec = modules.setdefault(k, [0, 0.0])
            rec[0] += 1
            rec[1] += m.dur * 1e-9
        starts = np.array([m.start for m in mods], np.int64)
        for e in op_ev:
            if not (lo <= e.start and e.end <= hi):
                continue
            label, opcode = op_label(e.name)
            if opcode not in CONTAINERS:
                ops[label] = ops.get(label, 0.0) + e.dur * 1e-9
            if COLLECTIVE.search(e.name) and len(mods):
                j = int(np.searchsorted(starts, e.start, side="right")) - 1
                if j >= 0 and e.start < mods[j].end:
                    k = _module_key(mods[j].name)
                    collective[k] = collective.get(k, 0.0) + e.dur * 1e-9
    for rec in modules.values():
        rec[0] //= n
        rec[1] /= n
    collective = {k: v / n for k, v in collective.items()}
    top = sorted(((k, v / n) for k, v in ops.items()), key=lambda kv: -kv[1])
    gaps = sorted(_gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])
    host = [e for e in events if HOST_SPAN.match(e.name)]
    named = [(_host_during(host, s, e), (e - s) * 1e-9)
             for s, e in gaps[:n_gaps]]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9 / n,
                   devices=n, modules=modules, collective_s=collective,
                   top_ops=top[:n_ops], idle_gaps=named)


def _gaps(spans, lo, hi) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in spans:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _host_during(host, s: int, e: int) -> str:
    """The host span that overlaps the gap ``[s, e)`` the most."""
    best, most = "host.other", 0
    for h in host:
        ov = min(e, h.end) - max(s, h.start)
        if ov > most:
            best, most = h.name, ov
    return best
