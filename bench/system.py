"""The system under test, built the way the serving CLI builds it, with
the benchmark's seeded weights.

One chip: ``ServingRuntime`` over an EP engine with a DanceMoE
``PlacementController`` (as ``launch/serve.build_server``). Four chips:
the ``EdgeCluster`` runtime backend with one EP rank per chip (as
``chip_smoke.py --four-chips``). ``build_engine`` takes no weights, so the
few lines that assemble the engine are mirrored here to hand it the
benchmark's own, which the configuration's architecture module makes
(``model_config`` and ``params``; see ``bench/arch/mixtral.py``).
"""

from __future__ import annotations

import dataclasses

import jax

from bench import weights as W


@dataclasses.dataclass
class System:
    """What the open-loop client talks to."""

    engine: object
    runtimes: list
    cluster: object = None
    runtime: object = None

    def submit(self, request):
        if self.cluster is not None:
            return self.cluster.submit(request)
        return self.runtime.enqueue(request)

    def step(self) -> None:
        if self.cluster is not None:
            self.cluster.step()
        else:
            self.runtime.step()

    def busy(self) -> bool:
        return any(r.queue or r.active or r._pending for r in self.runtimes)

    def counters(self) -> dict:
        keys = ("rounds", "ticks", "decode_rows", "prefill_rows",
                "prefix_hits", "prefix_tokens_skipped", "deferrals",
                "host_syncs", "prefill_calls", "chunks_executed")
        out = {k: sum(getattr(r, k) for r in self.runtimes) for k in keys}
        out["traces_after_warmup"] = sum(r.traces_after_warmup
                                         for r in self.runtimes)
        migs = self.cluster.migrations if self.cluster is not None else \
            self.runtime.migrations
        out["migrations"] = len(migs)
        return out


def build(arch, dims, cfg, serving: dict, max_len: int, seed: int,
          chips: int) -> System:
    """Engine, controller and runtime (or cluster) for one cell of the
    architecture module ``arch`` (its ``dims`` and the program's ``cfg``);
    the AOT warmup ladder compiles (or loads from the cache) here."""
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import build_controller, ep_capacities, step_rows
    from repro.models import moe as M
    from repro.models import transformer as tr
    from repro.serving.engine import ServingEngine

    mesh = make_test_mesh(1, chips)
    slots, bs = serving["max_slots"], serving["block_size"]
    rows = step_rows(max_len=max_len, slots=slots, block_size=bs)
    C, C2 = ep_capacities(rows, cfg.top_k, chips)
    spec = M.EPSpec.build(mesh, cfg, ep_axes=("model",), capacity=C,
                          slot_capacity=C2)
    rt = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="ep", ep_spec=spec,
                    dtype=W.DTYPE)
    params, experts = arch.params(W.base_key(seed), dims, mesh)
    expect = jax.eval_shape(
        lambda k: tr.init_params(dataclasses.replace(rt, moe_impl="dense",
                                                     ep_spec=None), k),
        jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), expect)
    if got != want:
        raise RuntimeError(f"parameter layout differs from the program's: "
                           f"{got} != {want}")
    _, n_groups = cfg.layer_pattern()
    pls = tr.stack_placement(
        M.uniform_placement(spec.n_ep, spec.slots, cfg.num_experts), n_groups)
    master = params["groups"]
    params = {**params, "groups": M.regather_ep_groups(
        master, pls, n_groups, sharding=experts if chips > 1 else None)}
    engine = ServingEngine(rt=rt, params=params, placement=pls,
                           dense_master=master, max_len=max_len)
    # a placement change re-gathers the expert slots: warm that program
    # here, so that the first migration does not compile in the window.
    # The engine must hold the only reference to its EP weights, or the
    # weight a migration replaces cannot be freed before the next one
    del params
    engine.migrate(pls)
    controller = build_controller(engine, serving["policy"],
                                  serving["review_rounds"])
    n_blocks = 1 + slots * -(-max_len // bs)
    opts = dict(max_slots=slots, block_size=bs, n_blocks=n_blocks,
                warmup=True)
    if chips == 1:
        from repro.serving.runtime import ServingRuntime

        runtime = ServingRuntime(engine, controller=controller,
                                 warmup_origins="untagged", **opts)
        return System(engine, [runtime], runtime=runtime)
    from repro.serving.cluster import EdgeCluster

    cluster = EdgeCluster("runtime", engine=engine, n_servers=chips,
                          controller=controller,
                          runtime_opts=dict(opts, warmup_origins="tagged"))
    return System(engine, cluster.backend.runtimes, cluster=cluster)
