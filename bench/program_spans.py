"""The program's own spans and scopes in a profiler trace, and the numbers
the benchmark reads from them.

The serving runtime wraps the host phases of each tick in ``serve.*``
annotations (``repro.serving.obs.host_span``: ``serve.tick`` around
``serve.admit``, ``serve.prefill``, ``serve.decode`` and ``serve.drain``,
which holds ``serve.wait``, ``serve.review`` and ``serve.migrate``), and the
kernels of its compiled steps in named scopes (``SCOPES``), which reach
the op-name path of each instruction's HLO metadata. Host spans and
device operations share the profiler's clock, so an idle gap on the
device can be named by what the program's host was doing in it.

A TPU trace names each operation by its HLO instruction and carries no
metadata, so the op-name paths come from the HLO text of the warmed
executables (``Compiled.as_text()``): each executable run is matched to
the executable whose instructions hold every operation it ran.

The requests' host-clock stamps (``RequestHandle.wall``) and the
runtime's counters come from the program too. A program that has none of
these (an older checkout) yields empty readings and ``None``, never an
error.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re

import numpy as np

from bench import reduce_trace as R

SCOPES = ("attn", "moe.route", "moe.dispatch", "moe.expert_ffn",
          "moe.combine", "head")
HOST_SPAN = re.compile(r"^serve\.")
STEPS = {"decode": r"jit__dec\b", "chunk": r"jit__chunk\b"}
# runtime counters the readings take window deltas of
COUNTERS = ("prefill_tokens", "prefill_calls", "ep_rows_dropped")
_SCOPE_IN_PATH = re.compile(
    r"(?:^|/)(" + "|".join(re.escape(s) for s in SCOPES) + r")(?=/|$)")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%?[\w.\-]+ = .*)$")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation and the op-name path of its HLO metadata."""

    plane: str
    name: str
    start: int   # ns
    dur: int     # ns
    op_name: str = ""

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    host: list       # reduce_trace.Event: the serve.* spans
    modules: list    # reduce_trace.Event: one per executable run
    ops: list        # Op


def scope_of(op_name: str) -> str | None:
    """The innermost of ``SCOPES`` on an op-name path, e.g.
    ``jit(_dec)/while/body/moe.expert_ffn/dot_general`` -> ``moe.expert_ffn``."""
    found = _SCOPE_IN_PATH.findall(op_name or "")
    return found[-1] if found else None


def _split(events) -> Trace:
    host, modules, ops = [], [], []
    for e in events:
        if R.DEVICE.match(e.plane) and e.line == R.MODULES_LINE:
            modules.append(e)
        elif R.DEVICE.match(e.plane) and e.line == R.OPS_LINE:
            ops.append(Op(e.plane, e.name, e.start, e.dur))
        elif not R.DEVICE.match(e.plane) and HOST_SPAN.match(e.name):
            host.append(e)
    return Trace(host, modules, ops)


def load_xplane(path) -> Trace:
    """The ``serve.*`` host spans, the executable runs and the operations
    of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return _split(R.Event(plane.name, line.name, e.name, int(e.start_ns),
                          int(e.duration_ns))
                  for plane in pd.planes for line in plane.lines
                  for e in line.events)


def load_json(path) -> tuple[Trace, list]:
    """The tests' form, as ``reduce_trace.load_json`` reads it, and the
    HLO texts it keeps under ``"hlo"`` (none in a file without them)."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    return (_split(R.Event(*e) for e in doc["events"]),
            list(doc.get("hlo", [])))


def hlo_op_names(text: str) -> dict:
    """``{operation label: op-name path}`` of every instruction in one
    executable's HLO text (``""`` where it carries no op name), labelled
    as ``reduce_trace.op_label`` labels a trace's operations."""
    out = {}
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            op_name = _OP_NAME.search(line)
            out[R.op_label(m.group(1))[0]] = op_name.group(1) if op_name else ""
    return out


def _by_run(trace: Trace) -> list:
    """Each device's operations grouped by the executable run they lie in,
    as ``[(run, ops)]``; ``run`` is None for operations outside any run."""
    out = []
    for p in sorted({o.plane for o in trace.ops}):
        mods = sorted((m for m in trace.modules if m.plane == p),
                      key=lambda m: m.start)
        starts = np.array([m.start for m in mods], np.int64)
        groups: dict[int, list] = {}
        for op in trace.ops:
            if op.plane != p:
                continue
            j = int(np.searchsorted(starts, op.start, side="right")) - 1
            groups.setdefault(j if j >= 0 and op.start < mods[j].end else -1,
                              []).append(op)
        out += [(mods[j] if j >= 0 else None, ops)
                for j, ops in groups.items()]
    return out


def name_ops(trace: Trace, tables: list) -> Trace:
    """The trace with each operation's op-name path taken from
    ``tables`` (``hlo_op_names`` of each warmed executable): a run's
    operations are looked up in the first table that holds every one of
    them, or else in the one that holds most."""
    keys = [set(t) for t in tables]
    named = []
    for run, ops in _by_run(trace):
        labels = {R.op_label(o.name)[0] for o in ops}
        best = max(range(len(tables)), default=None,
                   key=lambda i: (labels <= keys[i], len(labels & keys[i]),
                                  -i))
        table = tables[best] if run is not None and best is not None else {}
        named += [dataclasses.replace(
            o, op_name=o.op_name or table.get(R.op_label(o.name)[0], ""))
            for o in ops]
    return Trace(trace.host, trace.modules, named)


def scope_times(trace: Trace, pattern: str) -> tuple[dict, float]:
    """Device milliseconds per run of the executables matching ``pattern``
    under each scope (mean over chips; control-flow containers left out,
    their time being that of the operations inside), and the share of
    those runs' operation time that lies under some scope. ``({}, 0.0)``
    when no run matches or no operation carries a scope."""
    rx = re.compile(pattern)
    runs = sum(1 for m in trace.modules if rx.search(R._module_key(m.name)))
    per_scope: dict[str, float] = {}
    total = scoped = 0
    for run, ops in _by_run(trace):
        if run is None or not rx.search(R._module_key(run.name)):
            continue
        for op in ops:
            if R.op_label(op.name)[1] in R.CONTAINERS:
                continue
            total += op.dur
            scope = scope_of(op.op_name)
            if scope is not None:
                scoped += op.dur
                per_scope[scope] = per_scope.get(scope, 0) + op.dur
    if not runs or not scoped:
        return {}, 0.0
    return ({k: v * 1e-6 / runs for k, v in per_scope.items()},
            scoped / total)


def _depths(spans) -> dict:
    """Nesting depth of each host span within its thread (0 = a tick)."""
    depth = {}
    by_line: dict = {}
    for s in spans:
        by_line.setdefault((s.plane, s.line), []).append(s)
    for line in by_line.values():
        stack: list = []
        for s in sorted(line, key=lambda s: (s.start, -s.dur)):
            while stack and stack[-1].end <= s.start:
                stack.pop()
            depth[s] = len(stack)
            stack.append(s)
    return depth


def tick_self_ms(trace: Trace) -> list[float]:
    """Each ``serve.tick``'s milliseconds less the ``serve.wait`` inside
    it: the host's own work in the tick."""
    ticks = sorted((s for s in trace.host if s.name == "serve.tick"),
                   key=lambda s: s.start)
    waits = sorted((s for s in trace.host if s.name == "serve.wait"),
                   key=lambda s: s.start)
    starts = np.array([w.start for w in waits], np.int64)
    out = []
    for t in ticks:
        lo = int(np.searchsorted(starts, t.start, side="left"))
        waited = 0
        for w in waits[lo:]:
            if w.start >= t.end:
                break
            if w.plane == t.plane and w.line == t.line and w.end <= t.end:
                waited += w.dur
        out.append((t.dur - waited) * 1e-6)
    return out


def named_gaps(trace: Trace, n_gaps: int = 10) -> list:
    """The longest idle gaps of the first device, each named by the
    deepest ``serve.*`` span overlapping it (``host.other`` where none
    does), as ``[(name, seconds)]``, longest first."""
    planes = sorted({o.plane for o in trace.ops})
    if not planes:
        return []
    busy = R._union((o.start, o.end) for o in trace.ops
                    if o.plane == planes[0])
    gaps = sorted(R._gaps(busy, busy[0][0], busy[-1][1]),
                  key=lambda g: g[0] - g[1])[:n_gaps]
    depth = _depths(trace.host)
    out = []
    for s, e in gaps:
        over = [h for h in trace.host if h.start < e and h.end > s]
        best = max(over, key=lambda h: (depth[h], min(e, h.end)
                                        - max(s, h.start)), default=None)
        out.append((best.name if best else "host.other", (e - s) * 1e-9))
    return out


def prefill_waits(records, start: float, end: float) -> list | None:
    """Seconds from admission to first token, from each handle's ``wall``
    stamps, over the requests admitted in ``[start, end)``; one still
    prefilling at ``end`` counts with its wait so far. None when the
    program stamps no handle."""
    out, stamped = [], False
    for r in records:
        wall = getattr(r.handle, "wall", None)
        if wall is None:
            continue
        stamped = True
        a = wall.get("admitted")
        if a is None or not start <= a < end:
            continue
        first = wall.get("first_token")
        out.append((first if first is not None and first < end else end) - a)
    return out if stamped else None


def counters(runtimes) -> dict:
    """The ``COUNTERS`` summed over ``runtimes``; None for one the
    program lacks."""
    return {k: (sum(getattr(r, k) for r in runtimes)
                if all(hasattr(r, k) for r in runtimes) else None)
            for k in COUNTERS}


def deltas(c0: dict, c1: dict) -> dict:
    return {k: (None if c0.get(k) is None or c1.get(k) is None
                else c1[k] - c0[k]) for k in COUNTERS}


@dataclasses.dataclass
class Reading:
    """What the program's spans, scopes, stamps and counters say about a
    window."""

    scope_ms: dict           # "decode"/"chunk" -> {scope: device ms per run}
    scoped_share: dict       # "decode"/"chunk" -> share of op time scoped
    tick_self_ms: list       # per traced serve.tick
    gaps: list               # [(deepest serve.* span, seconds)]
    prefill_waits: list | None = None   # seconds, per request
    counters: dict = dataclasses.field(default_factory=dict)  # deltas


def reading(trace: Trace | None, records=(), start: float = 0.0,
            end: float = 0.0, c0: dict | None = None,
            c1: dict | None = None, hlo=()) -> Reading:
    """The reading of one window: ``trace`` (None when the run was not
    traced) and the HLO texts of the executables it ran, the client's
    records with the window's bounds, and the runtime ``counters`` at the
    window's start and end."""
    scope_ms, share = {}, {}
    if trace is not None and hlo:
        trace = name_ops(trace, [hlo_op_names(t) for t in hlo])
    if trace is not None:
        for kind, pattern in STEPS.items():
            scope_ms[kind], share[kind] = scope_times(trace, pattern)
    return Reading(
        scope_ms=scope_ms, scoped_share=share,
        tick_self_ms=tick_self_ms(trace) if trace is not None else [],
        gaps=named_gaps(trace) if trace is not None else [],
        prefill_waits=prefill_waits(records, start, end),
        counters=deltas(c0 or {}, c1 or {}))
