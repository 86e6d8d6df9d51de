"""Flash-crowd goodput benchmark: SLO-aware scheduling vs blind FIFO.

The scenario the streaming workload engine exists for: a diurnal request
stream over the non-uniform WAN testbed gets hit by a flash crowd —
several minutes of multiplied arrival rate pinned to the memory-poor WAN
server (origin 2) and concentrated on one *new* task profile, so both
the scheduler and the Eq.-4 placement review are under attack at once:

* the **SLO-aware leg** (``EdgeCluster(slo_aware=True)``) sheds requests
  no live server can start by their deadline and redirects the rest to
  the earliest-start server;
* the **FIFO leg** (the default) admits everything in arrival order and
  burns timeline on requests that were already doomed.

Both legs consume the *same seeded stream* (``WorkloadStream`` restarts
bit-identically), so the only difference is the scheduling policy.
Reported per leg: goodput (SLO-attained tokens per modeled second), SLO
attainment, sheds, and p50/p99 TTFT / inter-token latency split by
scenario phase (flash / peak / offpeak). The placement side is checked
too: the crowd's task shift must drive at least one completed migration
at or after the crowd's onset (``flash_migrations``).

Acceptance gates (asserted in ``smoke()`` and validated by the
``bench-serving/v7`` schema):

* SLO-aware goodput is **strictly** higher than FIFO goodput on the same
  stream;
* the SLO-aware leg sheds at least one request (the crowd really
  overloads the cluster);
* a full rerun of the SLO-aware leg reproduces every reported number
  bit-for-bit (``replay_identical``).

  PYTHONPATH=src python -m benchmarks.workload [--csv]

``smoke()`` returns the ``metrics.workload`` section of
``BENCH_serving.json`` on the same scenario for the CI ``bench-smoke``
job (the sim backend models time, so small and fast is still faithful).
Full mode also serves the flash-crowd stream through the *warmed
multi-server runtime backend* — a real jitted 3-server
``EdgeCluster("runtime")`` with the AOT bucket ladder and SLO-aware
admission, goodput reported per scenario phase — as a subprocess (see
``tests/md_scripts/workload_runtime_cluster.py``; the parent process
cannot re-configure the JAX device count once initialized).
"""
from __future__ import annotations

import sys

import numpy as np

from benchmarks.common import run_cpu_leg
from benchmarks.topology import (BENCH_PROFILE, _historical_stats,
                                 wan_testbed)
from repro.core.policies import ClusterView, PlacementController, get_policy
from repro.serving.api import EventType
from repro.serving.cluster import EdgeCluster
from repro.serving.net import CommCostModel, Topology
from repro.serving.workload import (FlashCrowd, WorkloadSpec, WorkloadStream,
                                    drive, goodput_report)

CROWD = FlashCrowd(start=40.0, duration=30.0, multiplier=6.0, origin=2,
                   fraction=0.9, task="flashtask")

BENCH_SPEC = WorkloadSpec(
    duration=120.0, base_rate=2.0, n_origins=3, origin_skew=0.8,
    diurnal_period=80.0, diurnal_amplitude=0.4, crowds=(CROWD,),
    prompt_len=(96.0, 0.6, 8, 384), output_len=(16.0, 0.5, 4, 48),
    slo=6.0, seed=0)


def _controller(topo: Topology, seed: int) -> PlacementController:
    pf = BENCH_PROFILE
    cm = CommCostModel(topology=topo, expert_bytes=pf.expert_bytes,
                       activation_bytes=pf.hidden_bytes_per_token,
                       tokens_per_horizon=1e5)
    return PlacementController(
        policy=get_policy("dancemoe"), cost=cm,
        cluster=ClusterView.from_topology(topo, pf),
        interval=20.0, topology=topo,
        stats=_historical_stats(topo, pf, seed))


def run_leg(spec: WorkloadSpec, slo_aware: bool, seed: int = 0) -> dict:
    """Serve one full pass of the seeded stream through the sim backend;
    returns the goodput report plus the leg's placement/shed counters."""
    topo = wan_testbed()
    ec = EdgeCluster("sim", topology=topo, profile=BENCH_PROFILE,
                     controller=_controller(topo, seed), seed=seed,
                     slo_aware=slo_aware)
    handles = drive(ec, WorkloadStream(spec), max_pending=64)
    rep = goodput_report(handles, phase_of=spec.phase_of)
    rep["deadline_redirects"] = int(
        getattr(ec.backend, "deadline_redirects", 0))
    rep["flash_migrations"] = sum(
        1 for e in ec.events
        if e.type == EventType.MIGRATION_COMPLETED
        and e.time >= spec.crowds[0].start)
    rep["mean_latency_by_origin"] = (
        ec.metrics()["per_server"]["mean_latency"])
    return rep


def measure(spec: WorkloadSpec = BENCH_SPEC, seed: int = 0) -> dict:
    """The three legs: SLO-aware, FIFO baseline, and the SLO-aware
    replay (bit-identity check) — all on the same seeded stream."""
    slo = run_leg(spec, slo_aware=True, seed=seed)
    fifo = run_leg(spec, slo_aware=False, seed=seed)
    replay = run_leg(spec, slo_aware=True, seed=seed)
    return {"slo": slo, "fifo": fifo,
            "replay_identical": int(replay == slo)}


def workload_section(results: dict, spec: WorkloadSpec) -> dict:
    """The ``metrics.workload`` section (since ``bench-serving/v7``)."""
    slo, fifo = results["slo"], results["fifo"]
    return {
        "n_servers": spec.n_origins,
        "requests": slo["requests"],
        "sheds": slo["sheds"],
        "deadline_redirects": slo["deadline_redirects"],
        "flash_migrations": slo["flash_migrations"],
        "goodput_tokens_per_s": slo["goodput_tokens_per_s"],
        "fifo_goodput_tokens_per_s": fifo["goodput_tokens_per_s"],
        "slo_attainment": slo["slo_attainment"],
        "fifo_slo_attainment": fifo["slo_attainment"],
        "ttft_s": slo["ttft"],
        "itl_s": slo["itl"],
        "phases": slo["phases"],
        "replay_identical": results["replay_identical"],
    }


def smoke(spec: WorkloadSpec = BENCH_SPEC) -> dict:
    """CI-gate measurement: the ``metrics.workload`` document section."""
    results = measure(spec)
    slo, fifo = results["slo"], results["fifo"]
    assert slo["goodput_tokens_per_s"] > fifo["goodput_tokens_per_s"], (
        "SLO-aware scheduling should beat blind FIFO on goodput for the "
        f"flash-crowd stream (got {slo['goodput_tokens_per_s']} vs "
        f"{fifo['goodput_tokens_per_s']})")
    assert slo["sheds"] >= 1, (
        "the flash crowd should force at least one shed — the scenario "
        "no longer overloads the cluster")
    assert slo["flash_migrations"] >= 1, (
        "the crowd's task shift should complete at least one placement "
        "migration at/after its onset — Eq.-4 review is not reacting")
    assert results["replay_identical"] == 1, (
        "rerunning the SLO-aware leg on the same seeded stream must "
        "reproduce every reported number bit-for-bit")
    return workload_section(results, spec)


def main(csv: bool = False):
    spec = BENCH_SPEC
    results = measure(spec)
    slo, fifo = results["slo"], results["fifo"]
    print(f"# flash-crowd workload ({slo['requests']} requests over "
          f"{spec.duration:.0f} s; crowd x{spec.crowds[0].multiplier:.0f} "
          f"at origin {spec.crowds[0].origin}, slo={spec.slo} s)")
    print(f"{'leg':10s} {'goodput tok/s':>14s} {'attainment':>11s} "
          f"{'sheds':>6s} {'ttft p99 (s)':>13s}")
    for name, leg in (("slo-aware", slo), ("fifo", fifo)):
        print(f"{name:10s} {leg['goodput_tokens_per_s']:14.3f} "
              f"{leg['slo_attainment']:11.3f} {leg['sheds']:6d} "
              f"{leg['ttft']['p99']:13.3f}")
    for ph, d in sorted(slo["phases"].items()):
        print(f"  phase {ph:8s}: {d['requests']:4d} req, "
              f"{d['sheds']:3d} shed, attainment {d['slo_attainment']:.3f}, "
              f"ttft p99 {d['ttft']['p99']:.3f} s")
    print(f"flash migrations: {slo['flash_migrations']}, "
          f"deadline redirects: {slo['deadline_redirects']}, "
          f"replay identical: {bool(results['replay_identical'])}")
    if csv:
        print(f"workload,slo_goodput,{slo['goodput_tokens_per_s']:.5f}")
        print(f"workload,fifo_goodput,{fifo['goodput_tokens_per_s']:.5f}")
        print(f"workload,sheds,{slo['sheds']}")
    assert slo["goodput_tokens_per_s"] > fifo["goodput_tokens_per_s"], (
        "SLO-aware scheduling should beat blind FIFO on goodput")
    print("# warmed multi-server runtime-backend leg (CPU-only: 3 fake "
          "host devices, subprocess; tick-clock goodput, not chip numbers)")
    out = run_cpu_leg("workload_runtime_cluster.py", 3, timeout=600.0)
    for line in out.strip().splitlines():
        if line.startswith(("platform:", "goodput:", "  phase", "zero-stall",
                            "ALL OK")):
            print(f"  {line}")


if __name__ == "__main__":
    main(csv="--csv" in sys.argv)
