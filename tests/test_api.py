"""Serving API v1: typed Request/Event contract, RequestHandle lifecycle,
the deprecated ``submit`` shim, decode compaction, the ``EdgeCluster``
façade on both backends, and cross-origin admission fairness.

This file must stay clean under ``-W error::DeprecationWarning`` (the CI
``strict-deprecations`` leg): every deliberate shim call is wrapped in
``pytest.warns``.
"""
import numpy as np
import pytest

from repro.core.policies import ClusterView, PlacementController, get_policy
from repro.serving.api import (Event, EventType, HomeRouter,
                               LeastLoadedRouter, Request, RequestHandle,
                               as_router)
from repro.serving.cluster import (DEEPSEEK_V2_LITE_PROFILE, EdgeCluster,
                                   paper_testbed, requests_from_workload)
from repro.serving.runtime import ServingRuntime

from test_paged_equivalence import BLOCK_SIZE, _engine, _reference


# ---------------------------------------------------------------------------
# Contract
# ---------------------------------------------------------------------------

def test_request_validation():
    p = [1, 2, 3]
    r = Request(prompt=p, max_new_tokens=2)
    assert r.prompt.dtype == np.int32 and r.prompt.shape == (3,)
    with pytest.raises(ValueError):
        Request(prompt=p, max_new_tokens=0)
    with pytest.raises(ValueError):
        Request(prompt=[], max_new_tokens=2)
    # temperature sampling is a first-class path now: > 0 is accepted,
    # only negative temperatures (and out-of-range seeds) are rejected
    assert Request(prompt=p, max_new_tokens=2, temperature=0.7,
                   seed=123).temperature == 0.7
    with pytest.raises(ValueError):
        Request(prompt=p, max_new_tokens=2, temperature=-0.1)
    with pytest.raises(ValueError):
        Request(prompt=p, max_new_tokens=2, seed=-1)
    with pytest.raises(ValueError):
        Request(prompt=p, max_new_tokens=2, seed=2 ** 31)
    with pytest.raises(ValueError):
        Request(prompt=p, max_new_tokens=2, slo=-1.0)
    with pytest.raises(ValueError):
        Request(prompt=p, max_new_tokens=2, origin=-1)


def test_handle_lifecycle_and_result_guard():
    h = RequestHandle(7, Request(prompt=[1], max_new_tokens=1))
    assert not h.done and h.tokens.size == 0 and h.metrics == {}
    with pytest.raises(RuntimeError):
        h.result()
    h._emit(EventType.ADMITTED, 3.0, server=1)
    assert h.admitted_at == 3.0 and h.server == 1
    # first writer wins: a cluster router's routing decision must not be
    # clobbered by the runtime's ADMITTED event (which reports the origin)
    h2 = RequestHandle(8, Request(prompt=[1], max_new_tokens=1))
    h2.server = 2                                  # router picked server 2
    h2._emit(EventType.ADMITTED, 0.0, server=0)    # runtime reports origin
    assert h2.server == 2
    h._emit(EventType.TOKEN, 4.0, token=42)
    h._emit(EventType.FINISHED, 5.0, latency=5.0, tokens=1)
    assert h.done and h.metrics["latency"] == 5.0
    np.testing.assert_array_equal(h.result(), [42])
    assert [e.type for e in h.events] == ["ADMITTED", "TOKEN", "FINISHED"]
    assert isinstance(h.events[0], Event)


def test_routers():
    loads = np.array([3.0, 1.0, 2.0])
    assert HomeRouter().route(2, loads) == 2
    assert HomeRouter().route(None, loads) == 1
    assert LeastLoadedRouter().route(2, loads) == 1
    assert isinstance(as_router("least-loaded"), LeastLoadedRouter)
    assert isinstance(as_router(None), HomeRouter)
    with pytest.raises(KeyError):
        as_router("nope")


# ---------------------------------------------------------------------------
# Runtime events + the deprecated submit shim
# ---------------------------------------------------------------------------

def test_event_stream_and_finished_metrics():
    eng, src, refs = _engine(False)
    p = src.sample(1, 12)[0]
    ref = _reference(eng, refs, p, 4)
    rtm = ServingRuntime(eng, max_slots=2, block_size=BLOCK_SIZE,
                         n_blocks=17)
    h = rtm.enqueue(Request(prompt=p, max_new_tokens=4, slo=100.0))
    rtm.run()
    np.testing.assert_array_equal(h.result(), ref)
    types = [e.type for e in h.events]
    assert types[0] == EventType.ADMITTED
    assert types[-1] == EventType.FINISHED
    assert types.count(EventType.TOKEN) == 4
    m = h.metrics
    assert m["tokens"] == 4 and m["latency"] >= 1 and m["wait"] >= 0
    assert m["slo_met"] is True and m["deferred_ticks"] == 0


def test_deferred_and_prefix_hit_events():
    eng, src, refs = _engine(False)
    prompt = src.sample(1, 24)[0]
    # pool fits one request at a time -> the second defers, then hits the
    # cached prefix of the first when admitted
    rtm = ServingRuntime(eng, max_slots=2, block_size=BLOCK_SIZE, n_blocks=5)
    h1 = rtm.enqueue(Request(prompt=prompt, max_new_tokens=3))
    h2 = rtm.enqueue(Request(prompt=prompt, max_new_tokens=3))
    rtm.run()
    assert h1.done and h2.done
    np.testing.assert_array_equal(h1.result(), h2.result())
    t2 = [e.type for e in h2.events]
    assert t2[0] == EventType.DEFERRED          # exactly one DEFERRED event
    assert t2.count(EventType.DEFERRED) == 1
    assert h2.deferred_ticks >= 1
    assert EventType.PREFIX_HIT in t2
    hit = next(e for e in h2.events if e.type == EventType.PREFIX_HIT)
    assert hit.data["tokens_skipped"] > 0
    assert h2.metrics["deferred_ticks"] == h2.deferred_ticks


def test_submit_shim_warns_and_is_token_identical():
    """The legacy positional surface is a DeprecationWarning shim over
    enqueue(): same admission, token-identical output."""
    eng, src, refs = _engine(False)
    p = src.sample(1, 16)[0]
    new = ServingRuntime(eng, max_slots=2, block_size=BLOCK_SIZE)
    h = new.enqueue(Request(prompt=p, max_new_tokens=5))
    new.run()
    old = ServingRuntime(eng, max_slots=2, block_size=BLOCK_SIZE)
    with pytest.warns(DeprecationWarning, match="enqueue"):
        rid = old.submit(p, 5)
    out = old.run()
    np.testing.assert_array_equal(out[rid], h.result())
    np.testing.assert_array_equal(out[rid],
                                  _reference(eng, refs, p, 5))
    # the shim still produces a live handle (one surface underneath)
    assert old.handles[rid].done


def test_simulator_router_shim_warns():
    from repro.serving.simulator import Router
    with pytest.warns(DeprecationWarning, match="HomeRouter"):
        Router(redirect=False)


# ---------------------------------------------------------------------------
# Decode compaction (satellite): bucketed active-slot batches
# ---------------------------------------------------------------------------

def test_compaction_token_identity_and_row_savings():
    """compact_decode on vs off: identical tokens, strictly fewer decode
    rows on a partially-occupied pool, invariants hold every tick."""
    eng, src, refs = _engine(False)
    jobs = [(src.sample(1, 12 + 4 * (k % 2))[0], 2 + k % 4, k)
            for k in range(5)]
    outs, rows, rounds = [], [], []
    for compact in (True, False):
        rtm = ServingRuntime(eng, max_slots=4, block_size=BLOCK_SIZE,
                             n_blocks=33, compact_decode=compact)
        handles = {}
        pending = list(jobs)
        t = 0
        while pending or rtm.queue or rtm.active:
            while pending and pending[0][2] <= t:
                p, s, _ = pending.pop(0)
                handles[len(handles)] = rtm.enqueue(
                    Request(prompt=p, max_new_tokens=s))
            rtm.step()
            rtm.check_invariants()
            t += 1
        outs.append([h.result() for h in handles.values()])
        rows.append(rtm.decode_rows)
        rounds.append(rtm.rounds)
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)
    assert rounds[0] == rounds[1]               # same schedule
    assert rows[1] == 4 * rounds[1]             # off: full width every round
    assert rows[0] < rows[1]                    # on: strictly fewer rows
    np.testing.assert_array_equal(outs[0][0], _reference(
        eng, refs, jobs[0][0], jobs[0][1]))


# ---------------------------------------------------------------------------
# EdgeCluster: runtime backend
# ---------------------------------------------------------------------------

def test_cluster_runtime_per_server_token_identity():
    """Per-server runtimes (own pools/batches) serve a routed stream
    token-identically to sequential generate()."""
    eng, src, refs = _engine(False)
    ec = EdgeCluster("runtime", engine=eng, n_servers=3,
                     shared_runtime=False,
                     runtime_opts=dict(max_slots=2, block_size=BLOCK_SIZE))
    with pytest.raises(ValueError, match="origin"):
        ec.submit(Request(prompt=src.sample(1, 8)[0], max_new_tokens=1,
                          origin=7))
    jobs = [(src.sample(1, 12)[0], 3, k % 3) for k in range(6)]
    handles = [ec.submit(Request(prompt=p, max_new_tokens=s, origin=n))
               for p, s, n in jobs]
    ec.run()
    for (p, s, n), h in zip(jobs, handles):
        np.testing.assert_array_equal(h.result(),
                                      _reference(eng, refs, p, s))
        assert h.server == n                   # home routing
    m = ec.metrics()
    assert m["per_server"]["submitted"] == [2, 2, 2]
    assert m["per_server"]["finished"] == [2, 2, 2]
    assert m["redirected_total"] == 0
    assert m["clock"] == "ticks"


def test_cluster_least_loaded_router_spreads_load():
    eng, src, refs = _engine(False)
    ec = EdgeCluster("runtime", engine=eng, n_servers=2,
                     shared_runtime=False, router="least-loaded",
                     runtime_opts=dict(max_slots=2, block_size=BLOCK_SIZE))
    p = src.sample(1, 12)[0]
    hs = [ec.submit(Request(prompt=p, max_new_tokens=2, origin=0))
          for _ in range(4)]
    ec.run()
    m = ec.metrics()
    assert sum(m["per_server"]["served"]) == 4
    assert m["per_server"]["served"][1] > 0    # traffic left its origin
    assert m["redirected_total"] > 0
    for h in hs:
        np.testing.assert_array_equal(h.result(),
                                      _reference(eng, refs, p, 2))


def test_cluster_shared_runtime_mode():
    eng, src, refs = _engine(False)
    ec = EdgeCluster("runtime", engine=eng, n_servers=3,
                     runtime_opts=dict(max_slots=3, block_size=BLOCK_SIZE))
    jobs = [(src.sample(1, 8)[0], 2, k % 3) for k in range(3)]
    handles = [ec.submit(Request(prompt=p, max_new_tokens=s, origin=n))
               for p, s, n in jobs]
    ec.run()
    for (p, s, n), h in zip(jobs, handles):
        np.testing.assert_array_equal(h.result(),
                                      _reference(eng, refs, p, s))
        assert h.request.origin == n           # caller's origin preserved
    # dense-MoE engine (n_ep=1) cannot attribute 3 origins: the cluster
    # serves untagged instead of mis-crediting
    assert not ec.backend.tag_origins


# ---------------------------------------------------------------------------
# Cross-origin admission fairness (satellite): FIFO deferral must not
# starve any origin when one server's stream is long-prompt-heavy
# ---------------------------------------------------------------------------

def test_fifo_deferral_does_not_starve_origins():
    eng, src, refs = _engine(False)
    # a pool tight enough that the long-prompt origin keeps deferring
    ec = EdgeCluster("runtime", engine=eng, n_servers=3,
                     runtime_opts=dict(max_slots=3, block_size=BLOCK_SIZE,
                                       n_blocks=13))
    handles: dict[int, list] = {0: [], 1: [], 2: []}
    # origin 0: long-prompt-heavy; origins 1, 2: short interactive
    for k in range(4):
        handles[0].append(ec.submit(Request(
            prompt=src.sample(1, 40)[0], max_new_tokens=6, origin=0)))
        handles[1].append(ec.submit(Request(
            prompt=src.sample(1, 8)[0], max_new_tokens=3, origin=1)))
        handles[2].append(ec.submit(Request(
            prompt=src.sample(1, 8)[0], max_new_tokens=3, origin=2)))
    ec.run()
    # pool pressure was real...
    assert sum(h.deferred_ticks for hs in handles.values() for h in hs) > 0
    # ...yet every origin's every request finished
    for hs in handles.values():
        assert all(h.done for h in hs)
    fin = {o: [h.metrics["latency"] for h in hs]
           for o, hs in handles.items()}
    # no starvation: the short origins complete ahead of the long one on
    # average, and symmetrically with each other (FIFO never lets the
    # long-prompt stream fence the pool off)
    assert np.mean(fin[1]) <= np.mean(fin[0])
    assert np.mean(fin[2]) <= np.mean(fin[0])
    sym = abs(np.mean(fin[1]) - np.mean(fin[2]))
    assert sym <= 0.5 * max(np.mean(fin[1]), np.mean(fin[2]))
    # and short requests interleave with the long stream rather than
    # queueing behind all of it
    assert min(min(fin[1]), min(fin[2])) < max(fin[0])


# ---------------------------------------------------------------------------
# EdgeCluster: sim backend
# ---------------------------------------------------------------------------

def test_cluster_sim_backend_matches_edge_simulator():
    """The sim backend is the same event-driven core: latencies from the
    typed API stream equal EdgeSimulator.run() on the source workload."""
    from repro.core.placement import dancemoe_placement
    from repro.data.traces import BIGBENCH_TASKS, poisson_workload
    from repro.serving.simulator import EdgeSimulator
    pf = DEEPSEEK_V2_LITE_PROFILE
    cl = paper_testbed(0.3)
    wl = poisson_workload(list(BIGBENCH_TASKS), num_layers=pf.num_layers,
                          num_experts=pf.num_experts,
                          mean_interarrival=20.0, duration=240.0, seed=0)
    cap = cl.expert_capacity(pf.expert_bytes)
    slots = np.minimum(np.maximum(cap // pf.num_layers, 1), pf.num_experts)
    plan = dancemoe_placement(wl.freqs_by_server(cl.n), cap, slots)
    ref = EdgeSimulator(cl, pf, wl, plan=plan, seed=1).run()

    ec = EdgeCluster("sim", spec=cl, profile=pf, plan=plan, tasks=wl.tasks,
                     seed=1)
    for r in requests_from_workload(wl):
        ec.submit(r)
    handles = ec.run()
    lat = np.array([h.metrics["latency"] for h in handles])
    np.testing.assert_allclose(lat, ref.latencies)
    assert all(h.done for h in handles)
    assert all(e.type in (EventType.ADMITTED, EventType.FINISHED)
               for h in handles for e in h.events)   # sim: no TOKEN events
    m = ec.metrics()
    assert len(m["per_server"]["local_ratio"]) == cl.n
    assert all(0.0 <= x <= 1.0 for x in m["per_server"]["local_ratio"])
    assert m["clock"] == "seconds"
    # routed/served bookkeeping agrees with the simulator's record
    served = np.bincount(ref.routed, minlength=cl.n)
    assert m["per_server"]["served"] == served.tolist()


def test_cluster_sim_origin_validation_and_fallback_routing():
    pf = DEEPSEEK_V2_LITE_PROFILE
    cl = paper_testbed(0.3)
    from repro.core.placement import dancemoe_placement
    cap = cl.expert_capacity(pf.expert_bytes)
    slots = np.minimum(np.maximum(cap // pf.num_layers, 1), pf.num_experts)
    rng = np.random.default_rng(0)
    plan = dancemoe_placement(
        rng.dirichlet(np.ones(pf.num_experts),
                      size=(pf.num_layers, cl.n)), cap, slots)
    ec = EdgeCluster("sim", spec=cl, profile=pf, plan=plan)
    # out-of-range origin fails at the submit site, not mid-simulation
    with pytest.raises(ValueError, match="origin"):
        ec.submit(Request(prompt=np.zeros(8, np.int32), max_new_tokens=1,
                          origin=7))
    # origin-less requests fall back to the least-loaded server: saturate
    # server 0, then an unattributed request must land elsewhere
    for _ in range(4):
        ec.submit(Request(prompt=np.zeros(512, np.int32),
                          max_new_tokens=64, origin=0, arrival=0.0))
    h = ec.submit(Request(prompt=np.zeros(8, np.int32), max_new_tokens=1,
                          arrival=1.0))
    ec.run()
    assert h.metrics["server"] != 0


def test_cluster_shared_mode_metrics_not_pinned_to_server0():
    """Shared-runtime mode has no routing decision: requests are recorded
    at their origin (round-robin when origin-less), never 'redirected' to
    a degenerate argmin(zeros) == server 0."""
    eng, src, refs = _engine(False)
    ec = EdgeCluster("runtime", engine=eng, n_servers=3,
                     router="least-loaded",
                     runtime_opts=dict(max_slots=3, block_size=BLOCK_SIZE))
    p = src.sample(1, 8)[0]
    for k in range(3):
        ec.submit(Request(prompt=p, max_new_tokens=2, origin=k))
    for _ in range(3):
        ec.submit(Request(prompt=p, max_new_tokens=2))   # origin-less
    ec.run()
    m = ec.metrics()
    assert m["per_server"]["served"] == [2, 2, 2]        # not [6, 0, 0]
    assert m["redirected_total"] == 0


def test_cluster_sim_slo_and_step():
    pf = DEEPSEEK_V2_LITE_PROFILE
    cl = paper_testbed(0.3)
    ctrl = PlacementController(policy=get_policy("dancemoe"), cost=None,
                               cluster=ClusterView.from_cluster(cl, pf),
                               interval=1e9)
    ec = EdgeCluster("sim", spec=cl, profile=pf, controller=ctrl)
    h1 = ec.submit(Request(prompt=np.zeros(64, np.int32), max_new_tokens=8,
                           origin=0, arrival=0.0, slo=1e9))
    h2 = ec.submit(Request(prompt=np.zeros(64, np.int32), max_new_tokens=8,
                           origin=1, arrival=1.0, slo=1e-12))
    assert ec.step() and h1.done and not h2.done    # event-by-event
    ec.run()
    assert h1.metrics["slo_met"] is True
    assert h2.metrics["slo_met"] is False


# ---------------------------------------------------------------------------
# The bench-serving schema, at its current version: cluster + net + perf +
# faults + tiers + workload + obs
# ---------------------------------------------------------------------------

def _schema():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import schema
    return schema


def _bench_doc():
    pair = {"cache": 2, "nocache": 1}
    return {
        "schema": _schema().SCHEMA_NAME, "mode": "smoke",
        "metrics": {
            "admitted_concurrency": dict(pair),
            "prefill_chunks_executed": dict(pair),
            "prefill_chunk_reduction": 2.0, "prefix_hits": 1,
            "prefill_tokens_skipped": 8, "cow_copies": 1,
            "deferrals": dict(pair),
            "decode_round_latency_s": {"mean": 0.1, "p95": 0.2},
            "mean_latency_ticks": dict(pair),
            "cluster": {
                "n_servers": 3,
                "per_server_admitted": [3, 4, 5],
                "per_server_routed": [3, 4, 5],
                "per_server_local_ratio": [0.5, 0.75, 1.0],
                "redirected_total": 0,
                "per_server_mem_gb": [12.0, 12.0, 24.0],
            },
            "net": {
                "n_servers": 3,
                "link_dispatch_bytes": [[0, 10, 20], [10, 0, 5],
                                        [20, 5, 0]],
                "cross_server_bytes": 70.0,
                "migration_transfer_seconds": 1.5,
                "migration_transfer_bytes": 3e6,
                "migrations_completed": 1,
                "per_server_mem_gb": [0.2, 0.2, 0.1],
                "per_server_expert_budget": [64, 64, 32],
            },
            "perf": {
                "warmup_seconds": 12.5,
                "executables_compiled": 7,
                "traces_after_warmup": 0,
                "host_syncs": 0,
                "rounds_timed": 40,
                "decode_round_ms": {"p50": 3.5, "p99": 9.0},
                "ttft_ms": {"p50": 120.0, "p99": 250.0},
                "ep_rows_dropped": 0,
                "ep_rows_computed": 2048,
                "ep_rows_filled": 512,
            },
            "faults": {
                "injected": 1,
                "recovered": 1,
                "tokens_lost": 0,
                "recovery_seconds": 0.25,
                "requests_dropped": 0,
                "baseline_tokens_lost": 200,
                "baseline_requests_dropped": 10,
                "replay_identical": 1,
            },
            "tiers": {
                "n_servers": 3,
                "per_server_gpu_slots": [48, 40, 24],
                "per_server_host_slots": [128, 112, 96],
                "per_server_gpu_resident": [48, 40, 24],
                "per_server_host_resident": [80, 72, 72],
                "promotions": 12,
                "demotions": 14,
                "prefetch_hit_ratio": 0.7,
                "on_demand_fetches": 200,
                "on_demand_stall_seconds": 4.2,
                "mean_latency_s": 0.29,
                "prefetch_off_mean_latency_s": 0.31,
                "prefetch_off_fetches": 240,
                "prefetch_off_stall_seconds": 4.9,
            },
            "workload": {
                "n_servers": 3,
                "requests": 480,
                "sheds": 140,
                "deadline_redirects": 90,
                "flash_migrations": 2,
                "goodput_tokens_per_s": 36.5,
                "fifo_goodput_tokens_per_s": 14.8,
                "slo_attainment": 0.49,
                "fifo_slo_attainment": 0.43,
                "ttft_s": {"p50": 1.2, "p99": 7.1},
                "itl_s": {"p50": 0.01, "p99": 0.05},
                "phases": {"flash": {"requests": 270, "sheds": 140}},
                "replay_identical": 1,
            },
            "obs": {
                "enabled": 1,
                "clock": "seconds",
                "events": 3528,
                "dropped_events": 0,
                "overhead_ms": 12.4,
                "span_counts": {"QUEUE_WAIT": 60, "PLACEMENT_REVIEW": 16},
                "replay_identical": 1,
            },
        },
    }


def test_schema_accepts_and_rejects():
    schema = _schema()
    assert schema.validate_bench_serving(_bench_doc())
    for mutate in (
        lambda d: d["metrics"].pop("cluster"),
        lambda d: d["metrics"]["cluster"].pop("per_server_local_ratio"),
        lambda d: d["metrics"]["cluster"].update(n_servers=2),   # len != n
        lambda d: d["metrics"]["cluster"].update(
            per_server_local_ratio=[0.5, 0.75, 1.5]),            # ratio > 1
        lambda d: d["metrics"]["cluster"].update(
            per_server_admitted=[0, 0, 0]),                      # empty run
        lambda d: d["metrics"]["cluster"].pop("per_server_mem_gb"),  # v3
        lambda d: d["metrics"].pop("net"),                       # v3
        lambda d: d["metrics"]["net"].pop("link_dispatch_bytes"),
        lambda d: d["metrics"]["net"].update(
            link_dispatch_bytes=[[0, 1], [1, 0]]),               # not n x n
        lambda d: d["metrics"]["net"].update(
            link_dispatch_bytes=[[0, 1, -2], [1, 0, 1],
                                 [1, 1, 0]]),                    # negative
        lambda d: d["metrics"]["net"].update(cross_server_bytes=0),  # empty
        lambda d: d["metrics"]["net"].pop("migration_transfer_seconds"),
        lambda d: d.update(schema="bench-serving/v6"),           # stale tag
        lambda d: d["metrics"].pop("perf"),                      # v4
        lambda d: d["metrics"]["perf"].pop("decode_round_ms"),
        lambda d: d["metrics"]["perf"]["decode_round_ms"].pop("p99"),
        lambda d: d["metrics"]["perf"].update(
            executables_compiled=0),                             # no warmup
        lambda d: d["metrics"]["perf"].update(
            decode_round_ms={"p50": 0.0, "p99": 0.0}),           # untimed
        lambda d: d["metrics"]["perf"].update(warmup_seconds=-1),
        lambda d: d["metrics"].pop("faults"),                    # v5
        lambda d: d["metrics"]["faults"].pop("recovery_seconds"),
        lambda d: d["metrics"]["faults"].update(injected=0),     # no fault
        lambda d: d["metrics"]["faults"].update(
            replay_identical=0),                                 # not bit-id
        lambda d: d["metrics"]["faults"].update(tokens_lost=-1),
        lambda d: d["metrics"].pop("tiers"),                     # v6
        lambda d: d["metrics"]["tiers"].pop("on_demand_stall_seconds"),
        lambda d: d["metrics"]["tiers"].update(promotions=0),    # no prefetch
        lambda d: d["metrics"]["tiers"].update(
            prefetch_hit_ratio=1.2),                             # ratio > 1
        lambda d: d["metrics"]["tiers"].update(
            per_server_gpu_slots=[48, 40]),                      # len != n
        lambda d: d["metrics"]["tiers"].update(on_demand_fetches=-1),
        lambda d: d["metrics"].pop("workload"),                  # v7
        lambda d: d["metrics"]["workload"].pop("goodput_tokens_per_s"),
        lambda d: d["metrics"]["workload"].pop("phases"),
        lambda d: d["metrics"]["workload"].update(phases={}),    # empty
        lambda d: d["metrics"]["workload"].update(requests=0),   # empty run
        lambda d: d["metrics"]["workload"].update(
            replay_identical=0),                                 # not bit-id
        lambda d: d["metrics"]["workload"].update(
            slo_attainment=1.2),                                 # ratio > 1
        lambda d: d["metrics"]["workload"].update(
            goodput_tokens_per_s=10.0),            # lost to FIFO: gate fails
        lambda d: d["metrics"]["workload"]["ttft_s"].pop("p99"),
        lambda d: d["metrics"]["workload"].update(sheds=-1),
        lambda d: d["metrics"]["perf"].pop("ep_rows_dropped"),
        lambda d: d["metrics"]["perf"].update(ep_rows_dropped=-1),
        lambda d: d["metrics"].pop("obs"),                       # v8
        lambda d: d["metrics"]["obs"].pop("overhead_ms"),
        lambda d: d["metrics"]["obs"].update(clock="wall"),
        lambda d: d["metrics"]["obs"].update(span_counts={}),
        lambda d: d["metrics"]["obs"].update(span_counts={"SHED": -1}),
        lambda d: d["metrics"]["obs"].update(events=0),          # empty run
        lambda d: d["metrics"]["obs"].update(dropped_events=3),
        lambda d: d["metrics"]["obs"].update(replay_identical=0),
    ):
        doc = _bench_doc()
        mutate(doc)
        with pytest.raises(schema.BenchSchemaError):
            schema.validate_bench_serving(doc)
