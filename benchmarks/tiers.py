"""Oversized-model serving through the expert tier hierarchy.

The headline scenario no baseline handles: the aggregate expert set does
NOT fit in aggregate GPU memory. Three heterogeneous servers hold 14
GPU expert slots per layer against the 16 experts each layer needs —
every layer has experts that live only in a host-RAM tier behind some
server's GPU. The benchmark serves the same skewed request stream (with
the mid-run task shift from ``benchmarks.topology``) through the
``EdgeCluster`` sim backend twice:

* **prefetch on** (default): the activation-aware prefetcher promotes
  experts that turn hot — e.g. after the task shift — into GPU residency
  over the host<->device link, overlapped with decode.
* **prefetch off**: tier residency is frozen at the initial
  hottest-first split; every activation on a back-tier expert keeps
  paying the on-demand host-fetch stall (or invokes a remote replica).

Reported (``metrics.tiers`` of ``BENCH_serving.json``, schema
``bench-serving/v6``): per-server per-tier slot capacities and
residency, promotion/demotion counts, the prefetch hit ratio,
on-demand-fetch stalls, and the prefetch-off comparison numbers. The CI
gate asserts prefetch-on gives *strictly* fewer on-demand stalls and
strictly lower mean latency.

  PYTHONPATH=src python -m benchmarks.tiers [--csv]

Full mode also runs the runtime-backend leg (real jitted engines on 3
fake CPU devices) as a subprocess — see ``tests/md_scripts/
tiers_runtime.py``; the parent process cannot re-configure the JAX
device count once initialized.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmarks.common import run_cpu_leg
from benchmarks.topology import BENCH_PROFILE, build_requests
from repro.core.policies import ClusterView, PlacementController, get_policy
from repro.serving.cluster import EdgeCluster
from repro.serving.net import CommCostModel, ServerProfile, Topology

# per-layer expert slots: GPU 6+5+3 = 14 < 16 experts/layer (oversized);
# host tiers hold the full set with room to spare
GPU_SLOTS = (6, 5, 3)
HOST_SLOTS = (16, 14, 12)


def _sharp_task_profile(name: str, idx: int, pf, seed: int):
    """A sharply skewed per-task activation profile (Zipf a in 2.2-2.8 vs
    the 0.3-1.6 library default), seeded off ``idx`` and ``seed``. Sparse
    gating tails are
    what makes on-demand-fetch counts residency-sensitive — see
    ``run_leg``."""
    from repro.data.traces import TaskProfile

    rng = np.random.default_rng([seed, idx, 77])
    L, E = pf.num_layers, pf.num_experts
    probs = np.zeros((L, E))
    for l in range(L):
        a = 2.2 + 0.6 * rng.random()
        z = 1.0 / (np.arange(E) + 1.0) ** a
        perm = rng.permutation(E)
        probs[l] = z[np.argsort(perm)] / z.sum()
    return TaskProfile(name=name, probs=probs)


def _primed_stats(topo: Topology, pf, seed: int):
    """Prime the controller with the first-phase task profiles (the
    paper's 'historical' statistics) — the tiered analogue of
    ``benchmarks.topology._historical_stats``, using the deterministic
    sharp profiles above."""
    from repro.core.stats import ActivationStats

    stats = ActivationStats(pf.num_layers, topo.n, pf.num_experts, decay=0.9)
    for n in range(topo.n):
        tp = _sharp_task_profile(f"task{n}", n, pf, seed)
        stats.update_server(n, tp.probs * 500.0 * pf.top_k)
    return stats


def tiered_testbed() -> Topology:
    """The WAN-ish 3-server testbed of ``benchmarks.topology``, with
    host-RAM expert tiers behind each GPU. Aggregate GPU slots per layer
    (14) < experts per layer (16): some experts exist *only* in host
    tiers — the oversized-model scenario."""
    pf = BENCH_PROFILE
    eb, L = pf.expert_bytes, pf.num_layers
    # PCIe-ish host links, slowest on the memory-poor WAN server
    host_bw = (2e9, 2e9, 1e9)
    profiles = tuple(
        ServerProfile(
            f"edge{i}",
            mem_bytes=GPU_SLOTS[i] * L * eb,
            host_mem_bytes=HOST_SLOTS[i] * L * eb,
            host_bw=host_bw[i],
        )
        for i in range(3)
    )
    bw = np.full((3, 3), 500e6 / 8)
    lat = np.full((3, 3), 2e-3)
    bw[0, 2] = bw[2, 0] = bw[1, 2] = bw[2, 1] = 25e6 / 8
    lat[0, 2] = lat[2, 0] = lat[1, 2] = lat[2, 1] = 40e-3
    np.fill_diagonal(lat, 0.0)
    return Topology(profiles, bw, lat)


def run_leg(n_requests: int, prefetch: bool, seed: int = 0) -> dict:
    """One sim-backend pass over the oversized cluster; returns the
    tiers metrics plus completion/latency numbers."""
    pf = BENCH_PROFILE
    topo = tiered_testbed()
    cm = CommCostModel(
        topology=topo,
        expert_bytes=pf.expert_bytes,
        activation_bytes=pf.hidden_bytes_per_token,
        tokens_per_horizon=1e5,
    )
    ctrl = PlacementController(
        policy=get_policy("dancemoe"),
        cost=cm,
        cluster=ClusterView.from_topology(topo, pf, tiered=True),
        interval=20.0,
        topology=topo,
        stats=_primed_stats(topo, pf, seed),
    )
    ec = EdgeCluster(
        "sim",
        topology=topo,
        profile=pf,
        controller=ctrl,
        seed=seed,
        prefetch=prefetch,
    )
    # Sharply skewed task profiles: each task concentrates on a handful
    # of hot experts, so a request's gating delta is *sparse* over the
    # 16-expert tail. With the post-shift hot set parked in host RAM, the
    # prefetch-off leg pays an on-demand fetch for those experts on every
    # request; the prefetch leg promotes them and stops paying. (Under
    # the default near-uniform tail, every back-tier cell fires every
    # request and the fetch count would be residency-invariant.)
    for t in range(2 * topo.n):
        name = f"task{t}"
        ec.backend.workload.tasks[name] = _sharp_task_profile(name, t, pf, seed)
    for r in build_requests(n_requests, 3, seed=seed):
        ec.submit(r)
    handles = ec.run()
    done = [h for h in handles if h.done]
    m = ec.metrics()
    return {
        "completed": len(done),
        "n_requests": len(handles),
        "mean_latency_s": float(np.mean([h.metrics["latency"] for h in done])),
        "tiers": m["tiers"],
    }


def measure(n_requests: int, seed: int = 0) -> dict:
    return {
        "prefetch": run_leg(n_requests, True, seed),
        "baseline": run_leg(n_requests, False, seed),
    }


def tiers_section(results: dict) -> dict:
    """The ``metrics.tiers`` section (since ``bench-serving/v6``): the
    prefetch leg's tier state + the prefetch-off comparison."""
    on, off = results["prefetch"], results["baseline"]
    out = dict(on["tiers"])
    out["mean_latency_s"] = round(on["mean_latency_s"], 6)
    out["prefetch_off_mean_latency_s"] = round(off["mean_latency_s"], 6)
    out["prefetch_off_fetches"] = off["tiers"]["on_demand_fetches"]
    out["prefetch_off_stall_seconds"] = off["tiers"]["on_demand_stall_seconds"]
    return out


def smoke(n_requests: int = 40) -> dict:
    """Small CI-gate measurement: the ``metrics.tiers`` document section,
    with the oversized-serving acceptance gates asserted."""
    pf = BENCH_PROFILE
    results = measure(n_requests)
    on, off = results["prefetch"], results["baseline"]
    assert on["completed"] == on["n_requests"], (
        f"oversized serving must complete every request "
        f"({on['completed']}/{on['n_requests']})"
    )
    assert off["completed"] == off["n_requests"], "prefetch-off leg incomplete"
    gpu_total = sum(on["tiers"]["per_server_gpu_slots"])
    assert gpu_total < pf.num_layers * pf.num_experts, (
        "scenario must be oversized: aggregate GPU slots "
        f"({gpu_total}) >= aggregate expert set "
        f"({pf.num_layers * pf.num_experts})"
    )
    assert on["tiers"]["promotions"] >= 1, (
        "the prefetcher never promoted an expert — nothing was measured"
    )
    assert (
        on["tiers"]["on_demand_stall_seconds"]
        < off["tiers"]["on_demand_stall_seconds"]
    ), (
        "prefetch must strictly reduce on-demand-fetch stalls: "
        f"{on['tiers']['on_demand_stall_seconds']} vs "
        f"{off['tiers']['on_demand_stall_seconds']}"
    )
    assert on["mean_latency_s"] < off["mean_latency_s"], (
        "prefetch must strictly reduce mean latency: "
        f"{on['mean_latency_s']} vs {off['mean_latency_s']}"
    )
    return tiers_section(results)


def main(csv: bool = False):
    n_requests = 60
    results = measure(n_requests)
    on, off = results["prefetch"], results["baseline"]
    pf = BENCH_PROFILE
    gpu_total = sum(on["tiers"]["per_server_gpu_slots"])
    print(
        f"# oversized model: {pf.num_layers * pf.num_experts} expert "
        f"instances over {gpu_total} aggregate GPU slots "
        f"({n_requests} requests, 3 servers)"
    )
    print(
        f"{'leg':14s} {'hit ratio':>10s} {'fetches':>8s} "
        f"{'stall (s)':>10s} {'promoted':>9s} {'latency (s)':>12s}"
    )
    for name, r in (("prefetch", on), ("no-prefetch", off)):
        t = r["tiers"]
        print(
            f"{name:14s} {t['prefetch_hit_ratio']:10.4f} "
            f"{t['on_demand_fetches']:8d} "
            f"{t['on_demand_stall_seconds']:10.3f} {t['promotions']:9d} "
            f"{r['mean_latency_s']:12.4f}"
        )
    if csv:
        for name, r in (("prefetch", on), ("baseline", off)):
            t = r["tiers"]
            print(f"tiers,{name}_stall_seconds,{t['on_demand_stall_seconds']}")
            print(f"tiers,{name}_mean_latency_s,{r['mean_latency_s']:.6f}")
        print(f"tiers,promotions,{on['tiers']['promotions']}")
    assert on["mean_latency_s"] < off["mean_latency_s"]
    print("# runtime-backend leg (CPU-only: 3 fake host devices, subprocess)")
    out = run_cpu_leg("tiers_runtime.py", 3, timeout=300.0).strip().splitlines()
    print(f"{out[0]}\n{out[-1]}")


if __name__ == "__main__":
    main(csv="--csv" in sys.argv)
