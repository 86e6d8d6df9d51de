"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, with ShapeDtypeStruct inputs (no allocation).

``main()`` appends ``--xla_force_host_platform_device_count=512`` to
``XLA_FLAGS`` before JAX creates its devices: the dry-run needs 512
placeholder host devices to build the (2, 16, 16) production mesh. Importing
the module changes nothing.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
import argparse
import dataclasses
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, InputShape, ModelConfig
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.placement import build_ep_placement, dancemoe_placement
from repro.launch import mesh as mesh_lib
from repro.launch.roofline import collective_bytes_from_hlo, roofline_report
from repro.models import moe as moe_mod
from repro.models import sharding as sh
from repro.models import transformer as tr
from repro.optim.adamw import adafactor
from repro.training.train_loop import make_train_step

def use_host_devices(n: int) -> None:
    """Ask for ``n`` placeholder host devices by appending to ``XLA_FLAGS``
    (call before anything creates a JAX device)."""
    flag = f"--xla_force_host_platform_device_count={n}"
    os.environ["XLA_FLAGS"] = f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip()


def ep_axes_for(cfg: ModelConfig) -> tuple[str, ...]:
    """MoE archs shard experts over the full in-pod device set."""
    return ("data", "model")


def make_runtime(cfg: ModelConfig, shape: InputShape, mesh, *,
                 moe_overrides: dict | None = None,
                 scan_layers: bool = True, layout: str = "tp",
                 remat_policy: str = "none",
                 kv_quant: bool = False) -> tr.Runtime:
    window = cfg.sliding_window if shape.name == "long_500k" else 0
    if cfg.family in ("ssm",):
        window = 0
    ep_spec = None
    if cfg.is_moe:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n_batch = int(np.prod([sizes[a] for a in sizes if a != "model"]))
        if shape.kind == "train" or shape.kind == "prefill":
            rows = shape.global_batch * shape.seq_len // (
                sizes["data"] * sizes["model"] * sizes.get("pod", 1))
        else:
            rows = max(shape.global_batch // max(n_batch, 1), 1)
        kw = dict(ep_axes=ep_axes_for(cfg), rows_per_rank=max(rows, 1),
                  capacity_factor=2.0)
        if shape.kind == "decode":
            btok = max(shape.global_batch // sizes.get("pod", 1), 1)
            kw["slot_capacity"] = max(
                16, int(np.ceil(btok * cfg.top_k / cfg.num_experts * 8)))
        if moe_overrides:
            kw.update(moe_overrides)
        ep_spec = moe_mod.EPSpec.build(mesh, cfg, **kw)
    return tr.Runtime(
        cfg=cfg, mesh=mesh,
        moe_impl="ep" if cfg.is_moe else "dense",
        ep_spec=ep_spec, dtype=jnp.bfloat16, window=window,
        scan_layers=scan_layers, layout=layout, remat_policy=remat_policy,
        kv_quant=kv_quant,
        cache_seq_sharded=(shape.name == "long_500k" and window == 0
                           and cfg.has_attention),
    )


def _sds(tree, spec_tree, mesh):
    """ShapeDtypeStruct pytree with NamedShardings attached."""
    def one(x, s):
        sp = sh._feasible_spec(mesh, x.shape, s)
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=NamedSharding(mesh, sp))
    return jax.tree.map(one, tree, spec_tree)


def placement_specs(cfg: ModelConfig, rt: tr.Runtime):
    """Stacked per-layer placement tables (device arrays; tiny)."""
    spec = rt.ep_spec
    E = cfg.num_experts
    _, n_groups = cfg.layer_pattern()
    freqs = np.random.default_rng(0).dirichlet(
        np.full(E, 0.5), size=(n_groups, spec.n_ep))
    cap = np.full(spec.n_ep, spec.slots * n_groups)
    plan = dancemoe_placement(freqs, cap, np.full(spec.n_ep, spec.slots))
    return build_ep_placement(plan, spec.slots)


def input_specs(cfg: ModelConfig, shape: InputShape, rt: tr.Runtime, mesh):
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    b_axes = tuple(a for a in mesh.axis_names if a != "model")
    B, T = shape.global_batch, shape.seq_len
    seq_ax = "model" if rt.layout in ("sp", "cp", "fsdp") else None
    out = {}
    if shape.kind == "train":
        tok = jax.ShapeDtypeStruct((B, T), jnp.int32)
        out["tokens"] = _sds(tok, P(b_axes, seq_ax), mesh)
        out["targets"] = _sds(tok, P(b_axes, seq_ax), mesh)
    elif shape.kind == "prefill":
        if cfg.frontend != "none":
            # modality stub: precomputed patch/frame embeddings
            emb = jax.ShapeDtypeStruct((B, T, cfg.d_model), jnp.bfloat16)
            out["embeds"] = _sds(emb, P(b_axes, None, None), mesh)
        else:
            tok = jax.ShapeDtypeStruct((B, T), jnp.int32)
            out["tokens"] = _sds(tok, P(b_axes, None), mesh)
    else:  # decode: one token against a seq_len cache
        cache = jax.eval_shape(
            lambda: tr.init_cache(rt, B, T, dtype=jnp.bfloat16))
        specs = sh.cache_pspecs(rt, seq_sharded=rt.cache_seq_sharded)
        out["cache"] = _sds(cache, specs, mesh)
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        out["tokens"] = _sds(tok, P(b_axes, None), mesh)
        out["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
    return out


def build_lowerable(cfg: ModelConfig, shape: InputShape, mesh,
                    scan_layers: bool = True, layout: str = "tp",
                    remat_policy: str = "none",
                    moe_overrides: dict | None = None,
                    kv_quant: bool = False):
    """Returns (jitted_fn, kwargs-of-ShapeDtypeStructs)."""
    rt = make_runtime(cfg, shape, mesh, scan_layers=scan_layers,
                      layout=layout, remat_policy=remat_policy,
                      moe_overrides=moe_overrides, kv_quant=kv_quant)
    pspec = lambda p: sh.pspecs_for(rt, p)
    params = jax.eval_shape(
        lambda: tr.init_params(rt, jax.random.PRNGKey(0)))
    params = _sds(params, pspec(params), mesh)
    kwargs = {"params": params}
    kwargs.update(input_specs(cfg, shape, rt, mesh))
    placement = None
    if cfg.is_moe:
        placement = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, P())),
            placement_specs(cfg, rt))
        kwargs["placement"] = placement

    if shape.kind == "train":
        opt = adafactor(schedule=None)
        step = make_train_step(rt, opt)
        opt_state = jax.eval_shape(
            lambda: opt.init(jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), params)))
        # factored states inherit the leading dims of their parameter spec
        flat_specs = jax.tree.leaves(pspec(params))

        def fact_spec(st, psp):
            if "vr" in st:
                return {"vr": P(*tuple(psp)[:-1]) if len(tuple(psp)) else P(),
                        "vc": P(*(tuple(psp)[:-2] + tuple(psp)[-1:]))}
            return {"v": psp}
        f_specs = [fact_spec(s, p) for s, p in
                   zip(opt_state["f"], flat_specs)]
        opt_specs = {"f": f_specs, "step": P()}
        kwargs = {"params": params,
                  "opt_state": _sds(opt_state, opt_specs, mesh),
                  "tokens": kwargs["tokens"], "targets": kwargs["targets"]}
        if placement is not None:
            kwargs["placement"] = placement

        def fn(params, opt_state, tokens, targets, placement=None):
            new_p, new_s, metrics = step(params, opt_state, tokens, targets,
                                         placement)
            return new_p, new_s, metrics["loss"]
        return fn, kwargs

    if shape.kind == "prefill":
        def fn(params, tokens=None, embeds=None, placement=None):
            logits, cache, _ = tr.prefill(rt, params, tokens=tokens,
                                          embeds=embeds, placement=placement)
            return logits, cache
        return fn, kwargs

    def fn(params, cache, tokens, pos, placement=None):
        logits, new_cache, _ = tr.decode_step(rt, params, cache, tokens, pos,
                                              placement)
        return logits, new_cache
    return fn, kwargs


def _unit_layers(cfg: ModelConfig) -> int:
    """Layers in one scan group (the depth-differencing unit)."""
    if cfg.family == "hybrid":
        return cfg.attn_every
    if cfg.family == "moe":
        return cfg.moe_every
    return 1


def depth_variant(cfg: ModelConfig, n_units: int) -> ModelConfig:
    return dataclasses.replace(cfg, num_layers=_unit_layers(cfg) * n_units)


def _analyse(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "collectives": collective_bytes_from_hlo(compiled.as_text())}


def _lower_compile(cfg, shape, mesh, scan_layers=True, **kw):
    fn, kwargs = build_lowerable(cfg, shape, mesh, scan_layers=scan_layers,
                                 **kw)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn).lower(**kwargs)
        return lowered.compile()


def depth_diff_analysis(cfg, shape, mesh, **build_kw) -> dict:
    """Exact full-depth per-device cost terms via depth differencing.

    XLA's cost analysis counts a scanned layer body once, so the scanned
    full model under-reports flops/bytes/collectives by ~n_groups. We lower
    UNROLLED 1-group and 2-group variants (both cheap to compile), take
    per_group = T(2) - T(1) and outside = T(1) - per_group, and extrapolate
    derived_full = outside + per_group * n_groups. Exact because every group
    lowers to identical HLO."""
    _, n_groups = cfg.layer_pattern()
    a1 = _analyse(_lower_compile(depth_variant(cfg, 1), shape, mesh,
                                 scan_layers=False, **build_kw))
    a2 = _analyse(_lower_compile(depth_variant(cfg, 2), shape, mesh,
                                 scan_layers=False, **build_kw))

    def extrap(x1, x2):
        per = max(x2 - x1, 0.0)
        outside = max(x1 - per, 0.0)
        return outside + per * n_groups

    coll = {}
    for k in a1["collectives"]:
        if k == "total_bytes":
            continue
        coll[k] = {
            "bytes": int(extrap(a1["collectives"][k]["bytes"],
                                a2["collectives"][k]["bytes"])),
            "count": int(extrap(a1["collectives"][k]["count"],
                                a2["collectives"][k]["count"])),
        }
    coll["total_bytes"] = sum(v["bytes"] for v in coll.values()
                              if isinstance(v, dict))
    return {"flops": extrap(a1["flops"], a2["flops"]),
            "bytes": extrap(a1["bytes"], a2["bytes"]),
            "collectives": coll,
            "depth1": a1, "depth2": a2}


def best_layout(cfg: ModelConfig, shape: InputShape) -> dict:
    """Best-known beyond-paper configuration per (arch, shape) from the
    §Perf hillclimb: cp for small models, fsdp (+placement-aware capacity)
    for large ones on train/prefill; decode is already memory-bound under
    the default layout. SSM archs keep tp (channel-sharded scan needs
    model-axis weights)."""
    if shape.kind == "decode" or cfg.family in ("ssm", "hybrid"):
        return {}
    kw: dict = {}
    if cfg.param_count() < 4e9:
        kw["layout"] = "cp"
    else:
        kw["layout"] = "fsdp"
    if shape.kind == "train":
        kw["remat_policy"] = "dots+kv"
    if cfg.is_moe:
        kw["moe_overrides"] = {"capacity_factor": 1.0}
    return kw


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            out_dir: str = "results/dryrun", save_hlo: bool = False,
            depth_diff: bool = True, optimized: bool = False) -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    build_kw = best_layout(cfg, shape) if optimized else {}
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(map(str, mesh.devices.shape)),
           "chips": n_chips, "ok": False, "build_kw": str(build_kw)}
    t0 = time.time()
    try:
        # 1) the deliverable: full model, scanned layers, lower + compile
        fn, kwargs = build_lowerable(cfg, shape, mesh, **build_kw)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn).lower(**kwargs)
            rec["lower_s"] = round(time.time() - t0, 1)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            rec[k] = int(getattr(mem, k, 0) or 0)
        scanned = _analyse(compiled)
        rec["hlo_flops_scanned"] = scanned["flops"]
        rec["hlo_bytes_scanned"] = scanned["bytes"]
        rec["collectives_scanned"] = scanned["collectives"]
        if save_hlo:
            Path(out_dir, f"{arch}__{shape_name}__{rec['mesh']}.hlo.txt"
                 ).write_text(compiled.as_text())
        del compiled

        # 2) exact per-device terms via depth differencing
        if depth_diff:
            dd = depth_diff_analysis(cfg, shape, mesh, **build_kw)
            rec["hlo_flops"] = dd["flops"]
            rec["hlo_bytes"] = dd["bytes"]
            rec["collectives"] = dd["collectives"]
        else:
            rec["hlo_flops"] = scanned["flops"]
            rec["hlo_bytes"] = scanned["bytes"]
            rec["collectives"] = scanned["collectives"]
        rec["roofline"] = roofline_report(rec, cfg, shape, n_chips=n_chips)
        rec["ok"] = True
    except Exception as e:  # record failures — they are bugs to fix
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = round(time.time() - t0, 1)
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    Path(out_dir, f"{arch}__{shape_name}__{rec['mesh']}.json").write_text(
        json.dumps(rec, indent=1, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="apply best-known layout per pair (see EXPERIMENTS"
                         " §Perf); write records to --out")
    args = ap.parse_args()
    use_host_devices(512)

    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_tag = "2x16x16" if mp else "16x16"
                done = Path(args.out, f"{arch}__{shape}__{mesh_tag}.json")
                if args.skip_done and done.exists():
                    prev = json.loads(done.read_text())
                    if prev.get("ok"):
                        print(f"[skip] {arch} {shape} {mesh_tag}")
                        continue
                rec = run_one(arch, shape, multi_pod=mp, out_dir=args.out,
                              optimized=args.optimized)
                status = "OK" if rec["ok"] else f"FAIL {rec.get('error')}"
                print(f"[{status}] {arch} {shape} {mesh_tag} "
                      f"({rec['total_s']}s)", flush=True)


if __name__ == "__main__":
    main()
