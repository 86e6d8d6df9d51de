"""One run of one cell: build, warm, measure an open-loop window, check the
served tokens against the reference, and report.

Everything that belongs to one configuration, traffic mix or metric is a
file found by name: ``BENCHMARK.json`` names the cell, the cell names its
configuration file and its traffic file (``bench/traffic/<traffic>.json``),
and each metric is read by ``bench/metrics/<metric>.py``. The configuration
file names its architecture module (``"arch"``: sizes, weights, the
program's model config and parameter tree, and the needed work of a step;
``bench/arch/mixtral.py`` says what it holds) and its float32 reference
(``"reference"``: ``forward_logits``), so this file knows no model.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import chip, compare, generator, openloop, stats
from bench import program_spans as PS
from bench.context import Context

ROOT = Path(__file__).resolve().parents[1]
# a traced run traces the last seconds of its window: enough steps for
# steady per-step times, and a trace that four chips' runs can read in
# well under the run's time limit
TRACE_SECONDS = 15.0


def load_cell(workload: str, root: Path = ROOT):
    """(benchmark, cell, configuration, traffic) for a workload name."""
    bm = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bm["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} (known: {sorted(cells)})")
    cell = cells[workload]
    confs = {c["name"]: c for c in bm["configs"]}
    conf = json.loads((root / confs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bm, cell, conf, traffic


def load_module(path, root: Path = ROOT):
    """The Python file at ``path`` (relative to ``root``) as a module,
    loaded once per process."""
    path = (root / path).resolve()
    name = "bench_file_" + "".join(ch if ch.isalnum() else "_"
                                   for ch in str(path))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def metric_reader(name: str, root: Path = ROOT):
    return load_module(Path("bench") / "metrics" / f"{name}.py", root).read


def metrics_of(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (untraced) or per-layer metrics
    (traced), each as its ``BENCHMARK.json`` entry."""
    e2e = [m for m in bm["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


# where the program's serving steps keep what the work counts read:
# {step: {"args": number of arguments, "stats": position of the MoE
# statistics in the output, "read": {name: (position, dtype, ndim)}}}. An
# architecture whose step takes other arguments brings its own ``LAYOUT``
DEFAULT_LAYOUT = {
    "dec": {"args": 11, "stats": 3,
            "read": {"pos": (4, "int32", 1), "mask": (7, "float32", 1)}},
    "chunk": {"args": 14, "stats": 4,
              "read": {"offsets": (7, "int32", 1), "mask": (10, "float32", 2)}},
}


class _Recorder:
    """Stands in for one warmed serving executable while the window is
    traced, keeping the small host-made inputs and the gating counts of
    each call (device arrays, read once the window has closed). A step
    whose signature moves fails the run rather than feeding other
    arguments to the counts."""

    def __init__(self, exe, key: tuple, log: dict, layout: dict = None):
        self.exe, self.kind, self.log = exe, key[0], log
        self.width, self.block_size = key[3], key[1]
        self.layout = (layout or DEFAULT_LAYOUT)[self.kind]

    def _take(self, args, at):
        i, dtype, ndim = at
        a = args[i]
        want = (self.width, self.block_size)[:ndim]
        if tuple(a.shape) != want or str(a.dtype) != dtype:
            raise RuntimeError(
                f"bench: the {self.kind} step's argument {i} is "
                f"{a.dtype}{list(a.shape)}, not {dtype}{list(want)}: the "
                f"program's step signature changed; update the layout")
        return a

    def __call__(self, *args):
        out = self.exe(*args)
        if self.log["on"]:
            n, stats = self.layout["args"], self.layout["stats"]
            if len(args) != n or not isinstance(out[stats], dict):
                raise RuntimeError(
                    f"bench: the {self.kind} step takes {len(args)} arguments "
                    f"(expected {n}) or returns no statistics at {stats}: the "
                    f"program's step signature changed; update the layout")
            counts = out[stats]["counts"]
            if counts.shape[-1] != self.log["experts"]:
                raise RuntimeError(
                    f"bench: the {self.kind} step's gating counts end in "
                    f"{counts.shape[-1]}, not the {self.log['experts']} experts")
            call = {k: self._take(args, at)
                    for k, at in self.layout["read"].items()}
            self.log["decode" if self.kind == "dec" else "chunk"].append(
                {**call, "counts": counts})
        return out


def _record_work(engine, log: dict, layout: dict = None) -> list[str]:
    """Wrap the engine's warmed steps in recorders; returns their HLO
    texts, which name the program's scopes in a trace."""
    hlo = []
    for key, exe in list(engine._compiled.items()):
        if key[0] in ("dec", "chunk"):
            hlo.append(exe.as_text())
            engine._compiled[key] = _Recorder(exe, key, log, layout)
    return hlo


def _work(log: dict, arch, dims, block_size: int) -> dict:
    """The architecture's needed work of every recorded call."""
    return {kind: [arch.work(dims, kind,
                             {k: np.asarray(v) for k, v in call.items()},
                             block_size)
                   for call in log[kind]]
            for kind in ("decode", "chunk")}


def _compile_counter() -> dict:
    import jax

    count = {"n": 0, "names": []}
    names = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/backend_compile_duration")

    def listen(event, duration, **kw):
        if event in names:
            count["n"] += 1
            count["names"].append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    return count


def _sample(records, k: int, seed: int) -> list:
    """Up to ``k`` finished requests: the longest, and the rest drawn from
    the seed."""
    done = [r for r in records if r.handle is not None and r.handle.done]
    if not done:
        return []
    size = [r.prompt_len + len(r.token_times) for r in done]
    first = int(np.argmax(size))
    rest = [i for i in range(len(done)) if i != first]
    rng = np.random.default_rng([seed, 1])
    pick = rng.permutation(rest)[: k - 1] if rest else []
    return [done[first]] + [done[i] for i in sorted(pick)]


def _check(conf, traffic, dims, seed, seqs, control, log, root=ROOT):
    """The served tokens of ``seqs`` against the configuration's float32
    reference: ``({name: {"value", "limit"}}, control readings or None)``."""
    if not seqs:
        return {}, None
    t_ref = time.perf_counter()
    length = -(-(traffic["prompt_len"][3] + traffic["output_len"][3]) // 128) * 128
    rows = int(traffic["check"]["requests"]) * int(traffic["output_len"][3])
    ref, ctl, margin = load_module(conf["reference"], root).forward_logits(
        dims, seed, seqs, length, rows, control=control)
    served = np.concatenate([s for _, s in seqs])
    g = compare.gaps(ref, served)
    far = g > 0.25
    log(f"gaps: mean {g.mean():.6g}, p50 {np.percentile(g, 50):.6g}, "
        f"p90 {np.percentile(g, 90):.6g}, p99 {np.percentile(g, 99):.6g}, "
        f"max {g.max():.6g} (not compared: near-ties swing it); "
        f"{int(far.sum())} of {len(g)} above 0.25, the reference's routing "
        f"margin there {np.round(np.sort(margin[far]), 5).tolist()} "
        f"against a median of {np.median(margin):.5g}", file=sys.stderr)
    checks = compare.limited(conf, g)
    control_out = None
    if ctl is not None:
        gc_ = compare.gaps(ref, ctl.argmax(-1))
        log(f"control gaps: mean {gc_.mean():.6g}, max {gc_.max():.6g}",
            file=sys.stderr)
        control_checks = compare.limited(conf, gc_)
        control_out = {"correct": compare.passes(control_checks),
                       "checks": control_checks}
    log(f"reference: {len(seqs)} requests, {len(served)} served tokens, "
        f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    return checks, control_out


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: Path = ROOT, chips_check: bool = True,
        cell_override=None, control: bool = False,
        rate: float | None = None, log=print) -> dict:
    """One run; returns the result object (the last line's JSON)."""
    import jax

    bm, cell, conf, traffic = (cell_override if cell_override is not None
                               else load_cell(workload, root))
    if rate is not None:
        traffic = {**traffic, "rate_per_s": rate}
    n_chips = int(cell["chips"])
    if chips_check:
        devices = chip.require_chips(n_chips)
        chip.use_compile_cache()
    else:
        devices = jax.devices()[:n_chips]
    d0 = devices[0]
    peaks = chip.peaks(d0.device_kind) if d0.platform == "tpu" else None
    compiles = _compile_counter()
    arch = load_module(conf["arch"], root)
    dims = arch.dims(conf)
    cfg = arch.model_config(conf, cell["config"])
    serving = traffic["serving"]
    max_len = generator.max_len(traffic)
    warm = float(traffic["warm_seconds"])
    requests = generator.stream(traffic, seed, warm, seconds, cfg.vocab_size)

    from bench import system as S

    system = S.build(arch, dims, cfg, serving, max_len, seed, n_chips)
    log(f"built in {time.perf_counter() - t_start:.3f} s since start; "
        f"{sum(r.executables_compiled for r in system.runtimes)} warmed "
        f"executables in {sum(r.warmup_seconds for r in system.runtimes):.3f} s; "
        f"bytes in use {[(d.memory_stats() or {}).get('bytes_in_use') for d in devices]}",
        file=sys.stderr)
    work_log = {"on": False, "decode": [], "chunk": [],
                "experts": cfg.num_experts}
    hlo = (_record_work(system.engine, work_log, getattr(arch, "LAYOUT", None))
           if trace else [])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    snap = {}

    def at_start():
        snap["c0"] = system.counters()
        snap["p0"] = PS.counters(system.runtimes)
        snap["compiles"] = compiles["n"]
        snap["queued0"] = sum(len(r.queue) for r in system.runtimes)

    def at_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        work_log["on"] = True

    def at_end():
        work_log["on"] = False
        snap["c1"] = system.counters()
        snap["p1"] = PS.counters(system.runtimes)
        snap["queued1"] = sum(len(r.queue) for r in system.runtimes)
        snap["compiled"] = sorted(set(compiles["names"][snap["compiles"]:]))
        snap["compiles"] = compiles["n"] - snap["compiles"]

    t0 = time.perf_counter()
    w0 = t0 + warm
    w1 = w0 + seconds
    marks = [(w0, at_start), (w1, at_end)]
    if trace:
        marks.append((w1 - min(seconds, TRACE_SECONDS), at_trace))
    records = openloop.drive(system, requests, t0, w1, marks=marks)
    if "c1" not in snap:
        at_end()
    if trace:
        jax.profiler.stop_trace()
    setup_s = w0 - t_start
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devices]
    win = stats.window(records, w0, w1)
    counters = {k: snap["c1"][k] - snap["c0"][k] for k in snap["c0"]}
    log(f"window {win.seconds:.3f} s: {win.attempted} requests due, "
        f"{win.tokens} tokens, {len(win.gaps)} gaps; counters {counters}; "
        f"programs traced or compiled in the window: {snap['compiles']} "
        f"{snap['compiled']}; "
        f"queued at start {snap['queued0']}, at end {snap['queued1']}",
        file=sys.stderr)

    summary = program = None
    if trace:
        program_trace = None
        if d0.platform == "tpu":
            from bench import reduce_trace as R

            xplane = R.find_xplane(trace_dir)
            summary = R.summarize(R.load_xplane(xplane))
            program_trace = PS.load_xplane(xplane)
            log(f"trace: {summary.window_s:.3f} s, busy {summary.busy_s:.3f} s, "
                f"modules {summary.modules}, collectives {summary.collective_s}",
                file=sys.stderr)
        program = PS.reading(program_trace, records, w0, w1, snap["p0"],
                             snap["p1"], hlo)
        log(f"program: scopes {program.scope_ms}, scoped share "
            f"{program.scoped_share}, counters {program.counters}, gaps "
            f"{program.gaps}", file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)
    work = (_work(work_log, arch, dims, serving["block_size"]) if trace
            else None)
    if work:
        log(f"recorded calls: decode {len(work['decode'])}, "
            f"chunk {len(work['chunk'])}", file=sys.stderr)

    ctx = Context(window=win, counters=counters, chips=n_chips, peaks=peaks,
                  trace=summary, work=work, program=program)
    metrics = {}
    for m in metrics_of(bm, workload, trace):
        if m["name"] == "setup_s":
            v = setup_s
        else:
            v = metric_reader(m["name"], root)(ctx)
        if v is None:
            continue
        entry = v if isinstance(v, dict) else {"value": v}
        metrics[m["name"]] = {"value": float(entry.pop("value")),
                              "unit": m["unit"], **entry}

    # -- correctness: once the window has closed and the program is freed
    sample = _sample(records, int(traffic["check"]["requests"]), seed)
    seqs = [(np.asarray(r.handle.request.prompt, np.int32),
             openloop.tokens_of(r)) for r in sample]
    del system, records, sample, work_log, program, ctx
    gc.collect()
    checks, control_out = _check(conf, traffic, dims, seed, seqs, control,
                                 log, root)
    result = {
        "correct": compare.passes(checks),
        "attempted": win.attempted,
        "failed": win.failed,
        "metrics": metrics,
        "device": {
            "platform": d0.platform,
            "kind": d0.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(max(mem)),
        },
    }
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.top_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps],
        }
    if control_out is not None:
        result["control"] = control_out
    result["checks"] = checks
    return result


def report(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The result's line, last on standard output, and each number
    compared beside its limit, last on standard error."""
    out.write(json.dumps(result) + "\n")
    out.flush()
    if not result["checks"]:
        err.write("check: no finished request to compare\n")
    control = result.get("control")
    if control is not None:
        for name, c in control["checks"].items():
            err.write(f"control {name} {c['value']!r} limit {c['limit']!r}\n")
        err.write(f"control correct {control['correct']}\n")
    for name, c in result["checks"].items():
        err.write(f"check {name} {c['value']!r} limit {c['limit']!r}\n")
    err.flush()
