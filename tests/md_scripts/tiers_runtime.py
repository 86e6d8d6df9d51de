"""Expert tier hierarchy on the runtime EdgeCluster backend (3 fake
devices, one EP rank per edge server).

The oversized-model scenario against the real jitted serving stack: the
plan assigns every server the full expert set, but each server's modeled
GPU tier holds only one expert per layer — the rest park in host RAM.
The engine keeps physical slots for every assigned expert (tiers are a
*modeled* residency overlay; the oversized constraint lives in the
``ServerProfile`` byte budgets, not in device memory), so tier state can
never break EP expert coverage.

Checks:
  1. serving completes every request, and the token streams are
     bit-identical to sequential ``generate()`` — tier bookkeeping and
     mid-run promotions must not change a single output token;
  2. the scenario is genuinely oversized (aggregate GPU slots < the
     expert set) and back-tier activations book on-demand fetches;
  3. the activation-aware prefetcher promotes at least one expert into
     GPU residency through the staged-transfer scheduler;
  4. reruns are bit-identical: token streams and the ``metrics.tiers``
     summary.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=3")

import jax
import numpy as np

from repro.configs import get_config
from repro.core.placement import PlacementPlan
from repro.core.policies import ClusterView, PlacementController, get_policy
from repro.data.pipeline import TaskTokenSource
from repro.launch.mesh import make_test_mesh
from repro.models import moe as M
from repro.models import transformer as tr
from repro.serving.api import Request
from repro.serving.cluster import EdgeCluster, MoEProfile
from repro.serving.engine import ServingEngine
from repro.serving.net import CommCostModel, ServerProfile, Topology

N_SERVERS, PROMPT, STEPS, N_REQUESTS = 3, 16, 6, 6


def build_engine():
    cfg = get_config("mixtral-8x7b").reduced()
    mesh = make_test_mesh(1, 3)
    # slots == num_experts: every rank can physically hold the full
    # assigned set, so the tier overlay never truncates coverage
    spec = M.EPSpec.build(
        mesh,
        cfg,
        ep_axes=("model",),
        slots=cfg.num_experts,
        capacity=4096,
        slot_capacity=8192,
    )
    _, n_groups = cfg.layer_pattern()
    rt = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="ep", ep_spec=spec)
    rt_dense = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="dense")
    params_dense = tr.init_params(rt_dense, jax.random.PRNGKey(0))
    pl0 = M.uniform_placement(spec.n_ep, spec.slots, cfg.num_experts)
    pls0 = tr.stack_placement(pl0, n_groups)
    params = dict(params_dense)
    params["groups"] = M.regather_ep_groups(params_dense["groups"], pls0, n_groups)
    engine = ServingEngine(
        rt=rt,
        params=params,
        placement=pls0,
        dense_master=params_dense["groups"],
        max_len=48,
    )
    return cfg, spec, n_groups, engine


def build_topology(cfg, n_groups):
    # GPU tier: 1 expert slot per layer per server (aggregate 3 < 4
    # experts per layer = oversized); host tier: the full set, fast
    # PCIe-ish host links so promotions land within a tick or two
    eb = 3 * cfg.d_model * cfg.d_ff * 2
    profiles = tuple(
        ServerProfile(
            f"e{i}",
            mem_bytes=n_groups * eb,
            host_mem_bytes=cfg.num_experts * n_groups * eb,
            host_bw=1e9,
        )
        for i in range(N_SERVERS)
    )
    bw = np.full((3, 3), 500e6 / 8)
    lat = np.full((3, 3), 2e-3)
    np.fill_diagonal(lat, 0.0)
    return Topology(profiles, bw, lat)


def full_replication_plan(n_groups, num_experts):
    assign = [
        [list(range(num_experts)) for _ in range(N_SERVERS)]
        for _ in range(n_groups)
    ]
    counts = np.full((n_groups, N_SERVERS), num_experts)
    return PlacementPlan(assign=assign, counts=counts, num_experts=num_experts)


def build_requests(cfg):
    reqs = []
    for k in range(N_REQUESTS):
        src = TaskTokenSource(f"edge{k}", cfg.vocab_size, seed=10 + k)
        prompt = src.sample(1, PROMPT)[0]
        reqs.append(Request(prompt=prompt, max_new_tokens=STEPS, origin=k % N_SERVERS))
    return reqs


def run_once(built=None):
    cfg, spec, n_groups, engine = built if built is not None else build_engine()
    topo = build_topology(cfg, n_groups)
    pf = MoEProfile(
        num_layers=n_groups,
        num_experts=cfg.num_experts,
        top_k=cfg.top_k,
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
    )
    cm = CommCostModel(
        topology=topo,
        expert_bytes=pf.expert_bytes,
        activation_bytes=pf.hidden_bytes_per_token,
        tokens_per_horizon=1e5,
    )
    # interval=1000: residency moves only through the tier prefetcher
    ctrl = PlacementController(
        policy=get_policy("dancemoe"),
        cost=cm,
        cluster=ClusterView.from_topology(topo, pf, tiered=True),
        interval=1000.0,
        topology=topo,
    )
    ctrl.plan = full_replication_plan(n_groups, cfg.num_experts)
    cluster = EdgeCluster(
        "runtime",
        engine=engine,
        n_servers=N_SERVERS,
        controller=ctrl,
        topology=topo,
        runtime_opts=dict(max_slots=4, prefix_cache=False),
    )
    requests = build_requests(cfg)
    handles = [cluster.submit(r) for r in requests]
    cluster.run()
    tokens = [h.result().tolist() if h.done else None for h in handles]
    return cluster, handles, tokens, cluster.metrics()


def main():
    print(f"platform: {jax.devices()[0].platform} x{len(jax.devices())}")
    built = build_engine()
    cfg, _, n_groups, engine = built

    cl1, h1, tok1, m1 = run_once(built=built)
    assert all(h.done for h in h1), "oversized serving must finish every request"
    t1 = m1["tiers"]
    assert sum(t1["per_server_gpu_slots"]) < n_groups * cfg.num_experts, t1
    assert all(
        r <= c
        for r, c in zip(t1["per_server_gpu_resident"], t1["per_server_gpu_slots"])
    ), t1
    assert sum(t1["per_server_host_resident"]) > 0, t1
    assert t1["on_demand_fetches"] > 0, t1
    assert 0.0 <= t1["prefetch_hit_ratio"] <= 1.0, t1
    print("oversized tier accounting OK:", t1)

    assert t1["promotions"] >= 1, (
        f"the prefetcher never promoted an expert on the runtime backend: {t1}"
    )
    print("prefetch promotions on runtime backend OK")

    # tiers are a modeled overlay: promotions re-apply the plan under the
    # new slot priority mid-run, which must not change any output token
    requests = build_requests(cfg)
    ref, _ = engine.generate(np.stack([r.prompt for r in requests]), steps=STEPS)
    for k in range(N_REQUESTS):
        np.testing.assert_array_equal(np.asarray(tok1[k], np.int32), ref[k])
    print("token identity under tier promotions OK")

    _, h2, tok2, m2 = run_once()
    assert tok1 == tok2, "token streams differ across reruns"
    assert m1["tiers"] == m2["tiers"], (m1["tiers"], m2["tiers"])
    print("rerun determinism OK")
    print("ALL OK")


if __name__ == "__main__":
    main()
