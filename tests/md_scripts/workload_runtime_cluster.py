"""Flash-crowd stream against the WARMED multi-server runtime backend.

The full-bench-mode leg behind ``benchmarks.workload.run_runtime_leg``
(and the PR-9 carryover): the seeded flash-crowd ``WorkloadStream`` is
served end-to-end by a real 3-server ``EdgeCluster("runtime")`` — one
jitted EP engine spanning 3 fake CPU devices, AOT bucket-ladder warmup,
SLO-aware scheduling on the tick clock, unified span tracing on — not
just the reduced single-server engine of ``workload_runtime.py``.

Checks:

  1. the warmed zero-stall contract holds under the crowd: the AOT
     ladder compiled at least one executable and the serving loop never
     retraced past warmup;
  2. the crowd overloads the cluster enough that SLO-aware admission
     sheds at least one request, while everything submitted resolves;
  3. goodput is reported **per scenario phase** (offpeak/peak/flash)
     from the same ``goodput_report`` the sim leg uses;
  4. tracing rode along without dropping events.

Runs as a subprocess (the parent bench process cannot re-configure the
JAX device count once initialized).
"""

import os

# one EP rank per edge server (standalone script — safe before jax init)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=3")

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import moe as M
from repro.models import transformer as tr
from repro.serving.cluster import EdgeCluster
from repro.serving.engine import ServingEngine
from repro.serving.workload import (FlashCrowd, WorkloadSpec,
                                    WorkloadStream, drive, goodput_report)

N_SERVERS = 3

# tick-clock scenario: arrivals land in submission order (the runtime
# backend queues at the submit tick); a serving wave takes a handful of
# ticks, so slo=26 ticks dooms the flash-crowd backlog tail
SPEC = WorkloadSpec(
    duration=60.0, base_rate=0.30, n_origins=N_SERVERS, origin_skew=0.8,
    diurnal_period=40.0, diurnal_amplitude=0.4,
    crowds=(FlashCrowd(start=20.0, duration=15.0, multiplier=5.0,
                       origin=2, fraction=0.9, task="flashtask"),),
    prompt_len=(12.0, 0.4, 8, 16), output_len=(6.0, 0.3, 4, 8),
    slo=26.0, seed=0)


def build_engine():
    cfg = get_config("mixtral-8x7b").reduced()  # 4 experts, top-2, 2 layers
    mesh = make_test_mesh(1, N_SERVERS)
    spec = M.EPSpec.build(mesh, cfg, ep_axes=("model",), slots=2,
                          capacity=4096, slot_capacity=8192)
    _, n_groups = cfg.layer_pattern()
    rt = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="ep", ep_spec=spec)
    rt_dense = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="dense")
    params_dense = tr.init_params(rt_dense, jax.random.PRNGKey(0))
    pl0 = M.uniform_placement(spec.n_ep, spec.slots, cfg.num_experts)
    pls0 = tr.stack_placement(pl0, n_groups)
    params = dict(params_dense)
    params["groups"] = M.regather_ep_groups(params_dense["groups"], pls0,
                                            n_groups)
    return ServingEngine(rt=rt, params=params, placement=pls0,
                         dense_master=params_dense["groups"], max_len=48)


def main():
    print(f"platform: {jax.devices()[0].platform} x{len(jax.devices())}")
    engine = build_engine()
    cluster = EdgeCluster(
        "runtime", engine=engine, n_servers=N_SERVERS, slo_aware=True,
        trace=True,
        runtime_opts=dict(max_slots=4, block_size=8, prefix_cache=False,
                          warmup=True, warmup_origins="tagged"))
    perf0 = cluster.backend.perf()
    print(f"warmup: {perf0['executables_compiled']} executables in "
          f"{perf0['warmup_seconds']:.1f}s")

    handles = drive(cluster, WorkloadStream(SPEC), max_pending=32)
    rep = goodput_report(handles)

    # 1. warmed zero-stall contract under the crowd
    perf = cluster.backend.perf()
    assert perf["executables_compiled"] >= 1
    assert perf["traces_after_warmup"] == 0, (
        f"the warmed loop retraced {perf['traces_after_warmup']} times")
    print(f"zero-stall OK: retraces={perf['traces_after_warmup']} "
          f"host_syncs={perf['host_syncs']}")

    # 2. the crowd forces sheds; every submission still resolves
    assert all(h.done for h in handles)
    assert rep["sheds"] >= 1, (
        f"flash crowd never forced a shed ({rep['requests']} requests) — "
        "the scenario no longer overloads the cluster")

    # 3. per-phase goodput from the shared accounting. The runtime
    # backend serves on the tick clock, so scenario phases are keyed on
    # each request's *stream arrival* (spec seconds), not its submit
    # tick — the sim leg's phase_of(submit) shortcut only works there
    # because sim submits land on the arrival timeline.
    by_phase: dict = {}
    for h in handles:
        by_phase.setdefault(SPEC.phase_of(h.request.arrival), []).append(h)
    assert len(by_phase) >= 2, (
        f"phase breakdown degenerate: {sorted(by_phase)}")
    print(f"goodput: {rep['goodput_tokens_per_s']:.3f} tok/tick "
          f"attainment={rep['slo_attainment']:.3f} sheds={rep['sheds']} "
          f"({rep['requests']} requests)")
    for ph, hs in sorted(by_phase.items()):
        d = goodput_report(hs)
        print(f"  phase {ph:8s}: {d['requests']:3d} req, "
              f"{d['sheds']:2d} shed, attainment {d['slo_attainment']:.3f}, "
              f"ttft p99 {d['ttft']['p99']:.1f} ticks")

    # 4. tracing rode the run without drops
    obs = cluster.metrics()["obs"]
    assert obs["dropped_events"] == 0
    assert obs["span_counts"].get("SHED", 0) >= 1
    print(f"trace OK: {obs['events']} spans, "
          f"sheds traced={obs['span_counts']['SHED']}")
    print("ALL OK")


if __name__ == "__main__":
    main()
