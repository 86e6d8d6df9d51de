"""Streaming workload engine, SLO-aware scheduling and goodput accounting.

Covers the workload subsystem end to end:

* ``WorkloadStream``: bit-identical restarts, laziness (no materialized
  trace), flash-crowd/diurnal/skew/task-shift structure, validation;
* the sim backend's SLO admission: sheds under the flash crowd, strict
  goodput win over the FIFO baseline on the same seeded stream, replay
  identity;
* the ``slo_met`` regression (the fault fast-forward used to mis-anchor
  the FINISHED latency at the *fast-forwarded* arrival instead of the
  submit time, silently flipping SLO verdicts);
* the runtime backend's EDF admission + shed path (SHED event contract);
* seeded Gumbel-max sampling: greedy identity at temperature 0,
  batch-composition independence, host/jit agreement;
* router properties under origin skew (hypothesis, satellite): the
  least-loaded router keeps every origin's p99 queue wait within the
  SLO, and home routing never shed-starves an origin outright;
* the jitted-runtime goodput/EDF/sampling leg as a subprocess
  (``md_scripts/workload_runtime.py``).

This file must stay clean under ``-W error::DeprecationWarning`` (the CI
``strict-deprecations`` leg).
"""
import dataclasses
import itertools
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.baselines import uniform_plan
from repro.serving.api import EventType, Request
from repro.serving.cluster import ClusterSpec, EdgeCluster, MoEProfile, ServerSpec
from repro.serving.workload import (FlashCrowd, WorkloadSpec, WorkloadStream,
                                    drive, goodput_report)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

PF = MoEProfile(num_layers=8, num_experts=16, top_k=2,
                d_model=512, d_ff=1024)

SPEC = WorkloadSpec(
    duration=80.0, base_rate=2.0, n_origins=3, origin_skew=0.8,
    diurnal_period=60.0, diurnal_amplitude=0.4,
    crowds=(FlashCrowd(start=25.0, duration=20.0, multiplier=6.0,
                       origin=2, fraction=0.9, task="flashtask"),),
    prompt_len=(96.0, 0.6, 8, 384), output_len=(16.0, 0.5, 4, 48),
    slo=6.0, seed=0)


def _sim_cluster(slo_aware: bool, router=None) -> EdgeCluster:
    """Plan-based sim cluster (no controller: these tests isolate the
    scheduling policy from the placement reviews)."""
    # 25 Mbps interconnect: remote expert dispatch dominates service time,
    # so the flash crowd genuinely overloads the cluster (500 Mbps serves
    # the whole stream inside the SLO and nothing would ever shed)
    spec = ClusterSpec(servers=tuple(
        ServerSpec(f"s{k}", mem_bytes=64 * PF.expert_bytes)
        for k in range(3)), bandwidth=25e6 / 8)
    plan = uniform_plan(PF.num_layers, 3, PF.num_experts)
    return EdgeCluster("sim", spec=spec, profile=PF, plan=plan,
                       router=router, slo_aware=slo_aware)


# ---------------------------------------------------------------------------
# WorkloadStream: determinism, laziness, structure
# ---------------------------------------------------------------------------

def test_stream_replays_bit_identically():
    a, b = list(WorkloadStream(SPEC)), list(WorkloadStream(SPEC))
    assert len(a) == len(b) > 100
    for x, y in zip(a, b):
        assert x.arrival == y.arrival and x.seed == y.seed
        assert x.origin == y.origin and x.task == y.task
        assert x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    # a different seed is a different stream
    c = list(WorkloadStream(dataclasses.replace(SPEC, seed=1)))
    assert [r.arrival for r in c] != [r.arrival for r in a]


def test_stream_is_lazy():
    """A multi-year scenario yields its head without materializing: the
    stream is a generator, not a list builder."""
    huge = dataclasses.replace(SPEC, duration=1e8, crowds=())
    head = list(itertools.islice(WorkloadStream(huge), 50))
    assert len(head) == 50
    assert all(head[i].arrival < head[i + 1].arrival for i in range(49))


def test_stream_structure():
    reqs = list(WorkloadStream(SPEC))
    phases = {p: [r for r in reqs if SPEC.phase_of(r.arrival) == p]
              for p in ("flash", "peak", "offpeak")}
    # the crowd multiplies the rate: the 20 s flash window out-arrives
    # the rest of the 80 s scenario combined
    assert len(phases["flash"]) > len(phases["peak"]) + len(phases["offpeak"])
    # ...and pins most of its requests to the crowd origin + task
    crowd = [r for r in phases["flash"] if r.task == "flashtask"]
    assert len(crowd) > 0.6 * len(phases["flash"])
    assert all(r.origin == 2 for r in crowd)
    assert not any(r.task == "flashtask" for r in reqs
                   if not SPEC.crowds[0].active(r.arrival))
    # Zipf skew outside the crowd: origin 0 strictly busiest
    rest = phases["peak"] + phases["offpeak"]
    counts = np.bincount([r.origin for r in rest], minlength=3)
    assert counts[0] > counts[1] > 0
    # every request carries the SLO and its own sampling seed
    assert all(r.slo == SPEC.slo for r in reqs)
    assert len({r.seed for r in reqs}) > 0.9 * len(reqs)
    # lengths respect the clip bounds
    assert all(8 <= len(r.prompt) <= 384 for r in reqs)
    assert all(4 <= r.max_new_tokens <= 48 for r in reqs)


def test_stream_task_shift():
    spec = dataclasses.replace(SPEC, crowds=(), task_shift_at=40.0)
    reqs = list(WorkloadStream(spec))
    before = {r.task for r in reqs if r.arrival < 40.0}
    after = {r.task for r in reqs if r.arrival >= 40.0}
    assert before <= {"task0", "task1", "task2"}
    assert after <= {"task3", "task4", "task5"}


def test_spec_validation():
    with pytest.raises(ValueError, match="base_rate"):
        WorkloadSpec(base_rate=0.0)
    with pytest.raises(ValueError, match="amplitude"):
        WorkloadSpec(diurnal_amplitude=1.0)
    with pytest.raises(ValueError, match="origin"):
        WorkloadSpec(n_origins=2, crowds=(FlashCrowd(0.0, 1.0, origin=5),))
    with pytest.raises(ValueError, match="multiplier"):
        FlashCrowd(0.0, 1.0, multiplier=0.5)
    with pytest.raises(ValueError, match="max_pending"):
        drive(_sim_cluster(False), [], max_pending=0)


# ---------------------------------------------------------------------------
# Sim backend: shed-on-overload, goodput win, replay identity
# ---------------------------------------------------------------------------

def _sim_leg(slo_aware: bool):
    ec = _sim_cluster(slo_aware)
    handles = drive(ec, WorkloadStream(SPEC), max_pending=32)
    return ec, handles, goodput_report(handles, phase_of=SPEC.phase_of)


def test_sim_slo_aware_beats_fifo_goodput():
    ec_s, h_s, rep_s = _sim_leg(True)
    ec_f, h_f, rep_f = _sim_leg(False)
    assert rep_s["requests"] == rep_f["requests"] > 100   # same stream
    # the crowd overloads the cluster: the SLO-aware leg sheds...
    assert rep_s["sheds"] >= 1
    assert ec_s.metrics()["sheds"] == rep_s["sheds"]
    assert rep_f["sheds"] == 0
    # ...and wins goodput strictly; FIFO still finishes everything (late)
    assert (rep_s["goodput_tokens_per_s"] > rep_f["goodput_tokens_per_s"])
    assert rep_f["finished"] == rep_f["requests"]
    assert rep_s["slo_attainment"] <= 1.0
    # shedding concentrates in the flash phase
    assert rep_s["phases"]["flash"]["sheds"] == rep_s["sheds"]
    # shed handles resolve empty with the SHED -> FINISHED contract
    shed = [h for h in h_s if h.metrics.get("shed")]
    assert len(shed) == rep_s["sheds"]
    for h in shed:
        assert h.done and h.metrics["tokens"] == 0
        assert h.metrics["slo_met"] is False
        assert [e.type for e in h.events][-2:] == [EventType.SHED,
                                                   EventType.FINISHED]
    # shed latencies must not pollute the cluster's serving latency means
    assert all(v >= 0.0 for v in ec_s.metrics()["per_server"]["mean_latency"])


def test_sim_replay_is_bit_identical():
    _, h1, rep1 = _sim_leg(True)
    _, h2, rep2 = _sim_leg(True)
    assert rep1 == rep2
    assert ([h.metrics for h in h1] == [h.metrics for h in h2])


def test_drive_bounds_backlog():
    """drive() must keep the backend's pending set at the cap, and reach
    the same result as unbounded submission."""
    ec = _sim_cluster(True)
    seen = []
    orig_submit = ec.submit

    def probe(req):
        seen.append(len(ec.backend._pending))
        return orig_submit(req)

    ec.submit = probe
    handles = drive(ec, WorkloadStream(SPEC), max_pending=8)
    assert max(seen) <= 8
    rep = goodput_report(handles, phase_of=SPEC.phase_of)
    _, _, ref = _sim_leg(True)
    assert rep == ref


# ---------------------------------------------------------------------------
# slo_met regression: the fault fast-forward must not move the SLO anchor
# ---------------------------------------------------------------------------

def test_slo_met_anchored_at_submit_time_under_fault_stall():
    """When a crash leaves experts with no live replica, arrivals are
    fast-forwarded to the recovery migration's eta. The FINISHED latency
    and the slo_met verdict must still be measured from the *submit*
    time — the pre-fix code measured from the fast-forwarded arrival,
    reporting latencies that were too small and slo_met=True on requests
    that actually blew their deadline."""
    from benchmarks.topology import BENCH_PROFILE, _historical_stats, build_requests
    from repro.core.policies import ClusterView, PlacementController, get_policy
    from repro.serving.faults import FaultSchedule
    from repro.serving.net import CommCostModel, ServerProfile, Topology
    pf = BENCH_PROFILE
    eb = pf.expert_bytes
    # server 2 holds experts exclusively (big memory), and the surviving
    # pair talks over a slow WAN hop — so its crash leaves uncovered
    # experts whose recovery transfers stall later arrivals
    profiles = (
        ServerProfile("edge0", mem_bytes=64 * eb, kv_mem_bytes=8e9,
                      compute_speed=50e12),
        ServerProfile("edge1", mem_bytes=64 * eb, kv_mem_bytes=8e9,
                      compute_speed=50e12),
        ServerProfile("big2", mem_bytes=128 * eb, kv_mem_bytes=4e9,
                      compute_speed=50e12),
    )
    bw = np.full((3, 3), 500e6 / 8)
    lat = np.full((3, 3), 2e-3)
    bw[0, 1] = bw[1, 0] = 10e6 / 8
    lat[0, 1] = lat[1, 0] = 40e-3
    np.fill_diagonal(lat, 0.0)
    topo = Topology(profiles, bw, lat)
    cm = CommCostModel(topology=topo, expert_bytes=eb,
                       activation_bytes=pf.hidden_bytes_per_token,
                       tokens_per_horizon=1e5)
    ctrl = PlacementController(policy=get_policy("dancemoe"), cost=cm,
                               cluster=ClusterView.from_topology(topo, pf),
                               interval=20.0, topology=topo,
                               stats=_historical_stats(topo, pf, 0))
    ec = EdgeCluster("sim", topology=topo, profile=pf, controller=ctrl,
                     seed=0, failover=True,
                     fault_schedule=FaultSchedule.server_crash(30.0, 2))
    reqs = [dataclasses.replace(r, slo=1.5)
            for r in build_requests(20, 3, seed=0)]
    handles = [ec.submit(r) for r in reqs]
    ec.run()
    stalled = 0
    for h in handles:
        m = h.metrics
        fin = next(e for e in h.events if e.type == EventType.FINISHED)
        start = next(e for e in h.events
                     if e.type == EventType.ADMITTED).time
        # the contract under test: latency and slo_met are anchored at
        # the submit time, whatever the fault machinery did in between
        assert m["latency"] == pytest.approx(fin.time - h.submitted_at)
        assert m["wait"] == pytest.approx(start - h.submitted_at)
        assert m["slo_met"] == (m["latency"] <= 1.5)
        if start - h.submitted_at > 0.2:       # fast-forward stall
            stalled += 1
    # the scenario is only a regression test if the stall really
    # happened AND pushed someone past the deadline
    assert stalled >= 1, "crash recovery never stalled an arrival"
    assert any(h.metrics["slo_met"] is False and h.metrics["latency"] > 1.5
               for h in handles)


# ---------------------------------------------------------------------------
# Runtime backend: EDF admission + shed (in-process, dense engine)
# ---------------------------------------------------------------------------

def test_runtime_shed_and_edf():
    from repro.serving.runtime import ServingRuntime
    from test_paged_equivalence import BLOCK_SIZE, _engine
    eng, src, _ = _engine(False)
    # one slot: the queue is real. The doomed request (needs 8 ticks,
    # 3-tick budget) must be shed without ever occupying the slot.
    rtm = ServingRuntime(eng, max_slots=1, block_size=BLOCK_SIZE,
                         slo_aware=True)
    blocker = rtm.enqueue(Request(prompt=src.sample(1, 8)[0],
                                  max_new_tokens=4))
    doomed = rtm.enqueue(Request(prompt=src.sample(1, 8)[0],
                                 max_new_tokens=8, slo=3.0))
    rtm.run()
    assert rtm.sheds == 1
    assert blocker.done and len(blocker.result()) == 4
    assert doomed.done and len(doomed.result()) == 0
    assert doomed.metrics["shed"] and doomed.metrics["slo_met"] is False
    shed_ev = next(e for e in doomed.events if e.type == EventType.SHED)
    assert shed_ev.data["deadline"] == 3.0
    # EDF: tighter deadline jumps the queue (admitted first)
    rtm2 = ServingRuntime(eng, max_slots=1, block_size=BLOCK_SIZE,
                          slo_aware=True)
    b = rtm2.enqueue(Request(prompt=src.sample(1, 8)[0], max_new_tokens=2))
    loose = rtm2.enqueue(Request(prompt=src.sample(1, 8)[0],
                                 max_new_tokens=2, slo=200.0))
    tight = rtm2.enqueue(Request(prompt=src.sample(1, 8)[0],
                                 max_new_tokens=2, slo=50.0))
    rtm2.run()
    assert b.done and loose.done and tight.done and rtm2.sheds == 0
    assert tight.admitted_at < loose.admitted_at
    # FIFO (default) is unchanged: same stream admits in arrival order
    rtm3 = ServingRuntime(eng, max_slots=1, block_size=BLOCK_SIZE)
    l2 = rtm3.enqueue(Request(prompt=src.sample(1, 8)[0],
                              max_new_tokens=2, slo=200.0))
    t2 = rtm3.enqueue(Request(prompt=src.sample(1, 8)[0],
                              max_new_tokens=2, slo=50.0))
    rtm3.run()
    assert l2.admitted_at < t2.admitted_at and rtm3.sheds == 0


# ---------------------------------------------------------------------------
# Seeded Gumbel-max sampling
# ---------------------------------------------------------------------------

def test_sampling_greedy_identity_and_determinism():
    import jax.numpy as jnp
    from repro.serving.sampling import sample_token_host, sample_tokens
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(4, 64)).astype(np.float32)
    zeros = jnp.zeros((4,), jnp.float32)
    seeds = jnp.asarray([5, 6, 7, 8], jnp.uint32)
    pos = jnp.asarray([3, 3, 9, 9], jnp.uint32)
    # temperature 0 rows are exact argmax
    out0 = np.asarray(sample_tokens(jnp.asarray(logits), zeros, seeds, pos))
    np.testing.assert_array_equal(out0, np.argmax(logits, -1))
    # a sampled row depends only on (logits, temp, seed, position) — not
    # on what else sits in the batch
    temps = jnp.full((4,), 0.9, jnp.float32)
    full = np.asarray(sample_tokens(jnp.asarray(logits), temps, seeds, pos))
    for j in range(4):
        solo = sample_token_host(logits[j], 0.9, int(seeds[j]), int(pos[j]))
        assert solo == full[j]
    # ...and reruns are bit-identical
    again = np.asarray(sample_tokens(jnp.asarray(logits), temps, seeds, pos))
    np.testing.assert_array_equal(full, again)
    # the draw actually varies with the key: across 64 seeds at a hot
    # temperature the same row yields more than one token
    row = logits[0]
    outs = {sample_token_host(row, 1.5, s, 0) for s in range(64)}
    assert len(outs) > 1


# ---------------------------------------------------------------------------
# Router properties under origin skew (hypothesis satellite)
# ---------------------------------------------------------------------------

@st.composite
def skew_instance(draw):
    seed = draw(st.integers(0, 2 ** 16))
    skew = draw(st.integers(10, 25))           # /10 -> 1.0 .. 2.5
    mult = draw(st.integers(4, 8))
    return WorkloadSpec(
        duration=40.0, base_rate=2.5, n_origins=3, origin_skew=skew / 10.0,
        diurnal_period=40.0, diurnal_amplitude=0.3,
        crowds=(FlashCrowd(start=10.0, duration=15.0, multiplier=float(mult),
                           origin=0, fraction=0.85),),
        prompt_len=(96.0, 0.5, 8, 256), output_len=(16.0, 0.4, 4, 32),
        slo=6.0, seed=seed)


@settings(max_examples=6, deadline=None)
@given(skew_instance())
def test_router_properties_under_skew(spec):
    # least-loaded + SLO admission: a served request is only admitted on
    # a server that can start it inside its budget, so every origin's
    # p99 queue wait stays within the SLO — skew cannot fence an origin
    # behind the hot server's backlog
    ec = _sim_cluster(True, router="least-loaded")
    handles = drive(ec, WorkloadStream(spec), max_pending=32)
    waits: dict[int, list] = {}
    for h in handles:
        m = h.metrics
        if m.get("shed") or m.get("wait") is None:
            continue
        waits.setdefault(h.request.origin, []).append(m["wait"])
    assert waits
    for origin, ws in waits.items():
        assert float(np.percentile(ws, 99)) <= spec.slo + 1e-6, (
            f"origin {origin} p99 wait blew the SLO under least-loaded")
    # home routing + SLO admission: the crowd may force sheds, but no
    # origin is starved outright — every origin gets served requests
    # (the deadline-redirect rule spills the hot origin's overflow)
    ec2 = _sim_cluster(True, router="home")
    handles2 = drive(ec2, WorkloadStream(spec), max_pending=32)
    served = {o: 0 for o in range(3)}
    submitted = {o: 0 for o in range(3)}
    for h in handles2:
        submitted[h.request.origin] += 1
        if not h.metrics.get("shed"):
            served[h.request.origin] += 1
    for o in range(3):
        if submitted[o] >= 3:
            assert served[o] >= 1, (
                f"home routing shed-starved origin {o}: "
                f"{served[o]}/{submitted[o]} served")


# ---------------------------------------------------------------------------
# goodput_report unit semantics
# ---------------------------------------------------------------------------

def test_goodput_report_math():
    from repro.serving.api import RequestHandle
    hs = []
    for k, (lat, met, tokens) in enumerate(
            [(2.0, True, 10), (9.0, False, 10), (0.0, None, 5)]):
        r = Request(prompt=np.zeros(4, np.int32), max_new_tokens=tokens,
                    slo=6.0 if met is not None else None, arrival=float(k))
        h = RequestHandle(k, r, clock="seconds")
        h.submitted_at = float(k)
        h._emit(EventType.ADMITTED, k + 0.5, server=0)
        h._emit(EventType.FINISHED, k + max(lat, 0.5), tokens=tokens,
                latency=max(lat, 0.5), wait=0.5, slo=r.slo, slo_met=met,
                shed=False, origin=None, server=0)
        hs.append(h)
    rep = goodput_report(hs, span=10.0)
    # good tokens: the met request (10) + the no-SLO request (5); the
    # late request's 10 tokens were wasted work
    assert rep["goodput_tokens_per_s"] == pytest.approx(1.5)
    assert rep["total_tokens"] == 25
    assert rep["slo_met"] == 1 and rep["slo_attainment"] == 0.5
    assert rep["ttft"]["p50"] > 0 and rep["itl"]["p99"] >= 0


# ---------------------------------------------------------------------------
# Jitted-runtime leg (subprocess: own engine, kept out of this process)
# ---------------------------------------------------------------------------

def test_runtime_goodput_subprocess():
    """The flash-crowd economics on the real jitted stack: EDF + shed
    beats FIFO on goodput, reruns (with temperature sampling) are
    bit-identical, temperature-0 rows equal greedy generate()."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).parent.parent / "src")
    script = Path(__file__).parent / "md_scripts" / "workload_runtime.py"
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (
        f"workload_runtime.py failed:\n{r.stdout}\n{r.stderr}")
    assert "ALL OK" in r.stdout


def test_token_streams_replay_across_processes():
    """Prompts and task profiles are seeded by a stable hash, not the
    per-interpreter salted ``hash()``: two processes with different
    ``PYTHONHASHSEED`` draw the same streams."""
    code = ("from repro.data.pipeline import TaskTokenSource; "
            "from repro.data.traces import make_task_profile; "
            "print(TaskTokenSource('t', 1000, seed=3).sample(2, 8).tolist(), "
            "make_task_profile('arith', 2, 4, seed=1).probs.round(12).tolist())")
    src = str(Path(__file__).resolve().parent.parent / "src")
    outs = [subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, check=True, timeout=120,
                           env={**os.environ, "PYTHONPATH": src,
                                "PYTHONHASHSEED": salt}).stdout
            for salt in ("1", "2")]
    assert outs[0] == outs[1] and outs[0].strip()
