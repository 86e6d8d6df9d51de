"""Production serving launcher: continuous-batching runtime for a selected
architecture (reduced variant on CPU; full config on TPU slices), with the
unified placement control plane active for MoE architectures.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --steps 8

Requests are submitted as a stream (staggered into the queue) and served by
``ServingRuntime``: chunked prefill interleaved with decode rounds over a
paged KV block pool (``--block-size`` / ``--blocks``; ``--dense-pool``
restores the legacy fixed-row pool), with the ``--policy`` placement policy
reviewed periodically by the ``PlacementController`` (Eq.-4 adopt
decision).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_configs
from repro.core.migration import CostModel
from repro.core.policies import (ClusterView, PlacementController,
                                 get_policy, list_policies)
from repro.data.pipeline import TaskTokenSource
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_test_mesh
from repro.models import moe as M
from repro.models import sharding as sh
from repro.models import transformer as tr
from repro.serving.api import Request
from repro.serving.engine import ServingEngine
from repro.serving.obs import Tracer
from repro.serving.runtime import ServingRuntime


def ep_capacities(rows: int, top_k: int, n_ep: int) -> tuple[int, int]:
    """Dropless EP capacities for steps of at most ``rows`` token rows:
    ``C`` holds one source rank's whole share of rows x ``top_k`` bound for
    a single destination, and ``C2`` every row of the step, since a slot
    holds one expert and a row sends it at most one (row, expert) pair.
    Both are upper bounds: each call clamps them by its own rows, so a
    32-row decode step computes 32 slot rows, not ``rows``."""
    return max(8, -(-rows // n_ep) * top_k), max(8, rows)


def step_rows(*, max_len: int, slots: int, block_size: int,
              dense_pool: bool = False) -> int:
    """The most token rows one engine step carries for a runtime of
    ``slots`` slots: a paged chunk round (slots x block_size) or one
    request's whole prompt (sequential ``generate()``, under max_len rows);
    the dense pool prefills up to ``slots`` same-length prompts at once."""
    if dense_pool:
        return slots * max_len
    return max(slots * block_size, max_len)


def build_engine(cfg, mesh, *, max_len: int, rows: int,
                 dtype=jnp.float32) -> ServingEngine:
    """The serving engine for ``cfg`` on ``mesh``, weights drawn from seed
    0, for steps of at most ``rows`` token rows (``step_rows``). An MoE
    model gets the EP layer (one rank per device of the ``model`` axis,
    uniform boot placement, capacities no step can overflow) and the dense
    master copy that live migration re-gathers expert slots from. On a
    multi-device mesh the master's experts and the EP slots are sharded
    over ``model`` and every other weight is replicated."""
    key = jax.random.PRNGKey(0)
    if not cfg.is_moe:
        rt = tr.Runtime(cfg=cfg, mesh=mesh, dtype=dtype)
        return ServingEngine(rt=rt, params=tr.init_params(rt, key),
                             placement=None, max_len=max_len)
    C, C2 = ep_capacities(rows, cfg.top_k, mesh.shape["model"])
    spec = M.EPSpec.build(mesh, cfg, ep_axes=("model",), capacity=C,
                          slot_capacity=C2)
    rt = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="ep", ep_spec=spec,
                    dtype=dtype)
    params = tr.init_params(dataclasses.replace(rt, moe_impl="dense",
                                                ep_spec=None), key)
    experts = None
    if mesh.size > 1:
        experts = NamedSharding(mesh, P(None, "model"))
        place = jax.tree.map(lambda _: NamedSharding(mesh, P()), params)
        for k, g in params["groups"].items():
            if "router" in g:
                for w in ("w1", "w3", "w2"):
                    place["groups"][k][w] = NamedSharding(mesh, sh._feasible_spec(
                        mesh, g[w].shape, experts.spec))
        params = jax.device_put(params, place)
    _, n_groups = cfg.layer_pattern()
    pls = tr.stack_placement(
        M.uniform_placement(spec.n_ep, spec.slots, cfg.num_experts), n_groups)
    master = params["groups"]
    params = {**params, "groups": M.regather_ep_groups(
        master, pls, n_groups, sharding=experts)}
    return ServingEngine(rt=rt, params=params, placement=pls,
                         dense_master=master, max_len=max_len)


def build_controller(engine: ServingEngine, policy: str,
                     interval: int) -> PlacementController | None:
    """The placement control plane of an MoE engine (None for dense
    models): ``policy`` reviewed every ``interval`` decode rounds."""
    rt = engine.rt
    if rt.ep_spec is None:
        return None
    cfg = rt.cfg
    cm = CostModel(expert_bytes=3 * cfg.d_model * cfg.d_ff * 2,
                   activation_bytes=cfg.d_model * 2, bandwidth=62.5e6,
                   tokens_per_horizon=1e5)
    return PlacementController(
        policy=get_policy(policy), cost=cm,
        cluster=ClusterView.from_ep_spec(rt.ep_spec, engine.n_groups),
        interval=interval)


def build_server(cfg, *, max_len: int, slots: int, block_size: int = 16,
                 blocks: int | None = None, dense_pool: bool = False,
                 prefix_cache: bool = True, warmup: bool = False,
                 policy: str = "dancemoe", review_rounds: int = 16,
                 dtype=jnp.float32, tracer: Tracer | None = None):
    """Engine, placement controller and continuous-batching runtime on a
    one-device mesh: the one construction path behind the serving CLI and
    ``chip_smoke.py``. Returns ``(engine, controller, runtime)``."""
    rows = step_rows(max_len=max_len, slots=slots, block_size=block_size,
                     dense_pool=dense_pool)
    engine = build_engine(cfg, make_test_mesh(1, 1), max_len=max_len,
                          rows=rows, dtype=dtype)
    controller = build_controller(engine, policy, review_rounds)
    if controller is not None and tracer is not None:
        controller.tracer = tracer          # PLACEMENT_REVIEW decisions
    runtime = ServingRuntime(engine, max_slots=slots, controller=controller,
                             paged=False if dense_pool else None,
                             block_size=block_size, n_blocks=blocks,
                             prefix_cache=prefix_cache, warmup=warmup,
                             warmup_origins="untagged", tracer=tracer)
    return engine, controller, runtime


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(list_configs()))
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch width (dense mode: also the KV rows)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV pool: positions per physical block")
    ap.add_argument("--blocks", type=int, default=None,
                    help="paged KV pool: physical blocks incl. the null "
                    "block (default: match the dense pool's KV memory)")
    ap.add_argument("--dense-pool", action="store_true",
                    help="legacy dense per-slot KV pool (no paging / "
                    "chunked prefill)")
    ap.add_argument("--prefix-cache", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="radix prefix cache: reuse KV pages across "
                    "requests sharing a prompt prefix (paged pool only; "
                    "--no-prefix-cache disables)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of system prompt shared by all requests "
                    "(demonstrates prefix-cache hits; 0 = disjoint prompts)")
    ap.add_argument("--warmup", action="store_true",
                    help="AOT-compile the full compaction bucket ladder "
                    "before serving and run the zero-stall decode loop "
                    "(paged pool only; pays compile time up front)")
    ap.add_argument("--policy", default="dancemoe", choices=list_policies())
    ap.add_argument("--review-rounds", type=int, default=16,
                    help="placement review period in decode rounds")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record per-request/system spans and export the "
                    "Chrome-trace JSON here (open at ui.perfetto.dev; "
                    "inspect with tools/trace_view.py)")
    ap.add_argument("--full", action="store_true",
                    help="published widths in bf16 (default: the reduced "
                    "float32 variant)")
    args = ap.parse_args()
    if args.shared_prefix and args.shared_prefix >= args.prompt:
        ap.error(f"--shared-prefix ({args.shared_prefix}) must be smaller "
                 f"than --prompt ({args.prompt}): the shared system prompt "
                 "is part of the per-request prompt budget")

    use_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    if args.warmup and args.dense_pool:
        ap.error("--warmup needs the paged pool (drop --dense-pool)")
    tracer = Tracer(clock="ticks") if args.trace_out else None
    engine, controller, runtime = build_server(
        cfg, max_len=args.prompt + args.steps + 8, slots=args.slots,
        block_size=args.block_size, blocks=args.blocks,
        dense_pool=args.dense_pool, prefix_cache=args.prefix_cache,
        warmup=args.warmup, policy=args.policy,
        review_rounds=args.review_rounds,
        dtype=jnp.bfloat16 if args.full else jnp.float32, tracer=tracer)
    if engine.rt.ep_spec is not None:
        spec = engine.rt.ep_spec
        print(f"EP capacities: C={spec.capacity} C2={spec.slot_capacity} "
              f"(slots={spec.slots} per rank)")
    if args.warmup:
        print(f"warmup: {runtime.executables_compiled} executables in "
              f"{runtime.warmup_seconds:.1f}s")
    src = TaskTokenSource("serve", cfg.vocab_size, seed=0)
    if cfg.frontend != "none":
        print(f"{cfg.name}: modality frontend is stubbed; serving over "
              "token prompts against the decoder backbone")
    shared = (src.sample(1, args.shared_prefix)[0]
              if args.shared_prefix else None)
    t0 = time.time()
    handles = []
    for _ in range(args.requests):
        tail = src.sample(1, max(args.prompt - args.shared_prefix, 1))[0]
        prompt = tail if shared is None else np.concatenate([shared, tail])
        handles.append(runtime.enqueue(Request(prompt=prompt,
                                               max_new_tokens=args.steps)))
    runtime.run()
    dt = time.time() - t0
    n_tok = sum(len(h.result()) for h in handles)
    pool = (f"paged[{runtime.allocator.n_blocks}x{runtime.block_size}]"
            if runtime.paged else f"dense[{args.slots}x{engine.max_len}]")
    cache = ("off" if runtime.prefix_cache is None else
             f"hit_rate={runtime.prefix_hit_rate:.2f} "
             f"tokens_skipped={runtime.prefix_tokens_skipped} "
             f"cow={runtime.cow_copies}")
    print(f"{cfg.name}: served {len(handles)} requests / {n_tok} tokens in "
          f"{dt:.1f}s ({n_tok / dt:.1f} tok/s) pool={pool} "
          f"peak_batch={runtime.max_concurrency} "
          f"peak_admitted={runtime.max_admitted} "
          f"deferrals={runtime.deferrals} "
          f"prefix_cache[{cache}] "
          f"migrations={len(runtime.migrations)}")
    if args.warmup:
        p = runtime.perf_metrics()
        print(f"zero-stall: traces_after_warmup={p['traces_after_warmup']} "
              f"host_syncs={p['host_syncs']} "
              f"decode_round_ms p50={p['decode_round_ms']['p50']:.2f} "
              f"p99={p['decode_round_ms']['p99']:.2f} "
              f"ttft_ms p50={p['ttft_ms']['p50']:.2f}")
    if tracer is not None:
        obs = tracer.summary()
        tracer.export(args.trace_out)
        print(f"trace: {obs['events']} spans "
              f"(dropped={obs['dropped_events']}, "
              f"overhead={obs['overhead_ms']:.2f}ms) -> {args.trace_out}")


if __name__ == "__main__":
    main()
