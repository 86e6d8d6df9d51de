"""Unified observability: span tracer, metrics registry, trace export.

Covers the ``repro.serving.obs`` contracts end to end:

* tracer primitives — recording, the max-events drop cap, the
  ``as_tracer`` normalization, the zero-allocation NULL_TRACER;
* the registry — provider collection, None-omission;
* trace determinism — a faulted + migrating + tiered sim scenario
  rerun exports **byte-identical** Chrome-trace JSON, and the cluster
  event stream keeps a seq-stamped stable total order (the
  ``EdgeCluster.events`` merge-ordering regression);
* span-tree well-formedness — per-request phase spans never overlap
  and every finished request closes its spans;
* the zero-host-sync contract — tracing on vs off over the warmed
  runtime: identical token streams, identical ``host_syncs``;
* the export surface — ``validate_trace_doc`` and
  ``tools/trace_view.py`` on a real exported file.
"""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving.api import EventType, Request
from repro.serving.obs import (NULL_TRACER, Registry, SpanKind, Tracer,
                               as_tracer)

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Tracer primitives
# ---------------------------------------------------------------------------

def test_tracer_records_spans_and_summary():
    tr = Tracer(clock="seconds")
    s = tr.span(SpanKind.QUEUE_WAIT, 1.0, 2.5, rid=3, server=1, shed=False)
    tr.instant(SpanKind.SHED, 2.5, rid=3, server=1)
    assert s.duration == 1.5 and s.seq == 0
    assert tr.by_kind(SpanKind.SHED)[0].start == tr.by_kind(SpanKind.SHED)[0].end
    assert [sp.kind for sp in tr.request_spans(3)] == [
        SpanKind.QUEUE_WAIT, SpanKind.SHED]
    out = tr.summary()
    assert out["enabled"] == 1 and out["clock"] == "seconds"
    assert out["events"] == 2 and out["dropped_events"] == 0
    assert out["span_counts"] == {"QUEUE_WAIT": 1, "SHED": 1}
    assert out["overhead_ms"] >= 0.0


def test_tracer_drop_cap():
    tr = Tracer(max_events=2)
    assert tr.span("A", 0, 1) is not None
    assert tr.span("A", 1, 2) is not None
    assert tr.span("A", 2, 3) is None          # over the cap: dropped
    assert len(tr.spans) == 2 and tr.dropped == 1
    assert tr.summary()["dropped_events"] == 1
    # dropped spans never consume sequence numbers (reruns with a larger
    # cap must not shift the retained seq stamps)
    assert [s.seq for s in tr.spans] == [0, 1]


def test_as_tracer_normalization():
    assert as_tracer(False, "ticks") is NULL_TRACER
    assert as_tracer(None, "seconds") is NULL_TRACER
    t = as_tracer(True, "seconds")
    assert isinstance(t, Tracer) and t.enabled and t.clock == "seconds"
    assert as_tracer(t, "seconds") is t
    with pytest.raises(ValueError, match="clock"):
        as_tracer(Tracer(clock="ticks"), "seconds")
    with pytest.raises(ValueError, match="clock"):
        Tracer(clock="wallclock")


def test_null_tracer_is_inert_and_refuses_export(tmp_path):
    assert not NULL_TRACER.enabled
    assert NULL_TRACER.span("A", 0, 1, rid=1) is None
    assert NULL_TRACER.instant("B", 0) is None
    assert NULL_TRACER.spans == [] and NULL_TRACER.summary()["events"] == 0
    with pytest.raises(RuntimeError, match="disabled"):
        NULL_TRACER.export(str(tmp_path / "t.json"))


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------

def test_registry_collects_in_order_and_omits_none():
    reg = Registry()
    reg.register("b", lambda: {"x": 1})
    reg.register("a", lambda: None)            # omitted this collection
    reg.register("c", lambda: {"y": 2})
    assert reg.namespaces == ("b", "a", "c")
    assert list(reg.collect().items()) == [("b", {"x": 1}), ("c", {"y": 2})]
    reg.register("b", lambda: {"x": 9})        # replace keeps the slot
    assert reg.collect()["b"] == {"x": 9}
    with pytest.raises(TypeError, match="callable"):
        reg.register("d", {"not": "callable"})


# ---------------------------------------------------------------------------
# Export determinism (property over random span batches)
# ---------------------------------------------------------------------------

@given(st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_trace_doc_deterministic_and_ordered(seed):
    def build():
        rng = np.random.default_rng(seed)
        tr = Tracer(clock="seconds")
        for _ in range(30):
            t0 = round(float(rng.uniform(0, 10)), 3)
            tr.span(str(rng.choice(SpanKind.ALL)), t0,
                    t0 + round(float(rng.uniform(0, 2)), 3),
                    rid=int(rng.integers(-1, 5)),
                    server=int(rng.integers(-1, 3)))
        return tr.to_trace_doc()

    doc, doc2 = build(), build()
    assert json.dumps(doc, sort_keys=True) == json.dumps(doc2, sort_keys=True)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    keys = [(e["ts"], e["args"]["seq"]) for e in xs]
    assert keys == sorted(keys)                # stable (ts, seq) order
    assert doc["otherData"]["spans"] == len(xs) == 30


# ---------------------------------------------------------------------------
# The faulted + migrating + tiered sim scenario
# ---------------------------------------------------------------------------

def _traced_sim_run(seed=0, n_requests=40):
    """One traced sim run with every span source active: the tiered WAN
    testbed, the dancemoe controller (staged migration), a timed link
    brownout, and tier prefetch (the ``benchmarks.obs`` scenario)."""
    from benchmarks.tiers import (_primed_stats, _sharp_task_profile,
                                  tiered_testbed)
    from benchmarks.topology import BENCH_PROFILE, build_requests
    from repro.core.policies import (ClusterView, PlacementController,
                                     get_policy)
    from repro.serving.cluster import EdgeCluster
    from repro.serving.faults import FaultSchedule
    from repro.serving.net import CommCostModel

    pf = BENCH_PROFILE
    topo = tiered_testbed()
    cm = CommCostModel(topology=topo, expert_bytes=pf.expert_bytes,
                       activation_bytes=pf.hidden_bytes_per_token,
                       tokens_per_horizon=1e5)
    ctrl = PlacementController(
        policy=get_policy("dancemoe"), cost=cm,
        cluster=ClusterView.from_topology(topo, pf, tiered=True),
        interval=20.0, topology=topo, stats=_primed_stats(topo, pf, seed))
    ec = EdgeCluster(
        "sim", topology=topo, profile=pf, controller=ctrl, seed=seed,
        fault_schedule=FaultSchedule.link_brownout(8.0, 0, 2, 0.3,
                                                   restore_at=30.0),
        trace=True)
    for t in range(2 * topo.n):
        name = f"task{t}"
        ec.backend.workload.tasks[name] = _sharp_task_profile(
            name, t, pf, seed)
    for r in build_requests(n_requests, 3, seed=seed):
        ec.submit(r)
    handles = ec.run()
    return ec, handles


@pytest.fixture(scope="module")
def traced_runs():
    """The scenario and its independent rerun (determinism witnesses)."""
    return _traced_sim_run(), _traced_sim_run()


def test_trace_rerun_byte_identical(traced_runs, tmp_path):
    (ec1, _), (ec2, _) = traced_runs
    p1 = ec1.export_trace(str(tmp_path / "a.json"))
    p2 = ec2.export_trace(str(tmp_path / "b.json"))
    b1, b2 = Path(p1).read_bytes(), Path(p2).read_bytes()
    assert b1 == b2
    assert len(b1) > 0


def test_all_span_sources_fired(traced_runs):
    (ec, _), _ = traced_runs
    counts = ec.metrics()["obs"]["span_counts"]
    for kind in (SpanKind.QUEUE_WAIT, SpanKind.PREFILL_CHUNK,
                 SpanKind.DECODE_ROUND, SpanKind.PLACEMENT_REVIEW,
                 SpanKind.TRANSFER_TASK, SpanKind.FAULT, SpanKind.PREFETCH,
                 SpanKind.COLD_FETCH_STALL):
        assert counts.get(kind, 0) >= 1, f"no {kind} spans"
    assert ec.metrics()["obs"]["dropped_events"] == 0


def test_span_trees_well_formed(traced_runs):
    """Per-request phase spans partition the request's service time:
    no strict overlaps, and every finished request closes its spans at
    or before its terminal event."""
    (ec, handles), _ = traced_runs
    eps = 1e-9
    by_rid: dict = {}
    for sp in ec.tracer.spans:
        if sp.rid >= 0:
            assert sp.end >= sp.start - eps    # no negative durations
            by_rid.setdefault(sp.rid, []).append(sp)
    assert by_rid, "no request spans recorded"
    for rid, spans in by_rid.items():
        spans = sorted(spans, key=lambda s: (s.start, s.end))
        for a, b in zip(spans, spans[1:]):
            assert b.start >= a.end - eps, (
                f"rid {rid}: {a.kind} [{a.start}, {a.end}] overlaps "
                f"{b.kind} [{b.start}, {b.end}]")
    for h in handles:
        assert h.done
        fin = [e for e in h.events
               if e.type in (EventType.FINISHED, EventType.SHED)]
        assert fin, f"rid {h.rid}: no terminal event"
        end = max(e.time for e in fin)
        spans = by_rid.get(h.rid, [])
        assert spans, f"rid {h.rid}: finished with no spans"
        kinds = {s.kind for s in spans}
        assert SpanKind.QUEUE_WAIT in kinds
        for s in spans:
            assert s.end <= end + eps, (
                f"rid {h.rid}: {s.kind} open past the terminal event "
                f"({s.end} > {end})")


def test_cluster_events_seq_total_order(traced_runs):
    """The ``EdgeCluster.events`` merge regression: every event carries
    a monotonic seq stamp, the merged list is sorted by (time, seq),
    and a rerun reproduces the exact total order — including events
    that coincide in time."""
    (ec1, _), (ec2, _) = traced_runs
    for ec in (ec1, ec2):
        ev = ec.events
        assert ev, "scenario produced no cluster events"
        seqs = [e.seq for e in ev]
        assert all(s >= 0 for s in seqs), "an event missed its seq stamp"
        assert len(set(seqs)) == len(seqs), "duplicate seq stamps"
        keys = [(e.time, e.seq) for e in ev]
        assert keys == sorted(keys)
        types = {e.type for e in ev}
        assert EventType.MIGRATION_STARTED in types
        assert EventType.LINK_DEGRADED in types
    order1 = [(e.type, round(e.time, 9), e.seq) for e in ec1.events]
    order2 = [(e.type, round(e.time, 9), e.seq) for e in ec2.events]
    assert order1 == order2


def test_metrics_registry_shape(traced_runs):
    """metrics() is registry-assembled but keeps the legacy shape; the
    obs section appears only when tracing is on."""
    (ec, _), _ = traced_runs
    m = ec.metrics()
    for key in ("backend", "clock", "n_servers", "per_server",
                "redirected_total", "sheds", "net", "tiers", "faults",
                "obs"):
        assert key in m, f"metrics() lost the {key!r} section"
    assert ec.registry.namespaces == ("cluster", "perf", "net", "tiers",
                                      "faults", "obs")
    assert m["obs"]["enabled"] == 1 and m["obs"]["clock"] == "seconds"


def test_untraced_cluster_has_no_obs_section():
    from repro.serving.cluster import (DEEPSEEK_V2_LITE_PROFILE,
                                       EdgeCluster, paper_testbed,
                                       requests_from_workload)
    from repro.core.placement import dancemoe_placement
    from repro.data.traces import BIGBENCH_TASKS, poisson_workload

    pf = DEEPSEEK_V2_LITE_PROFILE
    cl = paper_testbed(0.3)
    wl = poisson_workload(list(BIGBENCH_TASKS), num_layers=pf.num_layers,
                          num_experts=pf.num_experts,
                          mean_interarrival=30.0, duration=120.0, seed=0)
    cap = cl.expert_capacity(pf.expert_bytes)
    slots = np.minimum(np.maximum(cap // pf.num_layers, 1), pf.num_experts)
    plan = dancemoe_placement(wl.freqs_by_server(cl.n), cap, slots)
    ec = EdgeCluster("sim", spec=cl, profile=pf, plan=plan, tasks=wl.tasks)
    for r in requests_from_workload(wl):
        ec.submit(r)
    ec.run()
    m = ec.metrics()
    assert "obs" not in m                      # NULL_TRACER: no section
    assert ec.tracer is NULL_TRACER
    with pytest.raises(RuntimeError, match="disabled"):
        ec.export_trace("/dev/null")


# ---------------------------------------------------------------------------
# Export surface: schema validation + the textual viewer
# ---------------------------------------------------------------------------

def test_validate_trace_doc_accepts_real_export_rejects_tampered(
        traced_runs, tmp_path):
    from benchmarks.schema import BenchSchemaError, validate_trace_doc

    (ec, _), _ = traced_runs
    doc = json.loads(Path(ec.export_trace(
        str(tmp_path / "t.json"))).read_text())
    assert validate_trace_doc(doc) is doc
    for tamper in (
        lambda d: d.pop("otherData"),
        lambda d: d["otherData"].__setitem__("dropped", 3),
        lambda d: d["otherData"].__setitem__("spans", 1),
        lambda d: d.__setitem__("traceEvents", []),
        lambda d: d["traceEvents"][-1].pop("ts"),
        lambda d: d["traceEvents"][-1]["args"].pop("seq"),
    ):
        bad = json.loads(json.dumps(doc))
        tamper(bad)
        with pytest.raises(BenchSchemaError):
            validate_trace_doc(bad)


def test_trace_view_renders_breakdown(traced_runs, tmp_path):
    (ec, _), _ = traced_runs
    path = ec.export_trace(str(tmp_path / "t.json"))
    r = subprocess.run(
        [sys.executable, str(REPO / "tools" / "trace_view.py"), path,
         "--top", "3"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    for token in ("phase", "QUEUE_WAIT", "DECODE_ROUND", "server0",
                  "control-plane", "slowest"):
        assert token in r.stdout, f"viewer output missing {token!r}"


# ---------------------------------------------------------------------------
# Zero-host-sync contract on the warmed runtime
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def warm_engine():
    import jax
    from repro.configs import get_config
    from repro.data.pipeline import TaskTokenSource
    from repro.launch.mesh import make_test_mesh
    from repro.models import transformer as tr
    from repro.serving.engine import ServingEngine

    cfg = get_config("mixtral-8x7b").reduced()
    mesh = make_test_mesh(1, 1)
    rt = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="dense")
    params = tr.init_params(rt, jax.random.PRNGKey(0))
    eng = ServingEngine(rt=rt, params=params, placement=None, max_len=48)
    src = TaskTokenSource("obs", cfg.vocab_size, seed=7)
    return eng, src


def _serve_warmed(eng, requests, tracer):
    from repro.serving.runtime import ServingRuntime

    rtm = ServingRuntime(eng, max_slots=2, block_size=8, prefix_cache=False,
                         warmup=True, warmup_origins="untagged",
                         tracer=tracer)
    handles = [rtm.enqueue(Request(prompt=r.prompt,
                                   max_new_tokens=r.max_new_tokens))
               for r in requests]
    rtm.run()
    return rtm, [h.result().tolist() for h in handles]


def test_tracing_adds_no_host_syncs_and_keeps_tokens(warm_engine):
    """The acceptance gate: tracing on vs off over the warmed zero-stall
    loop — token streams identical, ``host_syncs`` unchanged (batch
    spans are recorded from launch-side metadata only)."""
    eng, src = warm_engine
    requests = [Request(prompt=src.sample(1, 8 + 4 * (k % 2))[0],
                        max_new_tokens=3 + k)
                for k in range(4)]
    tracer = Tracer(clock="ticks")
    rtm_on, toks_on = _serve_warmed(eng, requests, tracer)
    rtm_off, toks_off = _serve_warmed(eng, requests, None)
    assert toks_on == toks_off
    p_on, p_off = rtm_on.perf_metrics(), rtm_off.perf_metrics()
    assert p_on["host_syncs"] == p_off["host_syncs"]
    assert p_on["traces_after_warmup"] == p_off["traces_after_warmup"] == 0
    # the traced leg actually recorded the batch-level phases
    counts = tracer.summary()["span_counts"]
    assert counts.get(SpanKind.QUEUE_WAIT, 0) == len(requests)
    assert counts.get(SpanKind.PREFILL_CHUNK, 0) >= 1
    assert counts.get(SpanKind.DECODE_ROUND, 0) >= 1
    # batch spans carry no per-request payloads (rid = -1): completion
    # data rides the async drain, never a fresh device sync
    assert all(s.rid == -1 for s in tracer.by_kind(SpanKind.DECODE_ROUND))
