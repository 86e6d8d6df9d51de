"""Perf-iteration driver (§Perf): lower a (arch, shape) pair under a named
variant (sharding layout / remat policy / MoE capacity override), derive the
roofline terms via depth differencing, and append the record to
results/perf/<arch>__<shape>.jsonl — the hypothesis -> change -> measure log.

  PYTHONPATH=src python -m repro.launch.perf --arch tinyllama-1.1b \
      --shape train_4k --variant cp --note "replicated weights + ctx parallel"
"""
import argparse
import json
import time
from pathlib import Path

import jax

from repro.configs import get_config
from repro.configs.base import INPUT_SHAPES
from repro.launch import mesh as mesh_lib
from repro.launch.dryrun import (_analyse, _lower_compile, build_lowerable,
                                 depth_variant, use_host_devices)
from repro.launch.roofline import roofline_report

VARIANTS = {
    # name -> kwargs for build_lowerable
    "baseline": {},
    "sp": {"layout": "sp"},
    "cp": {"layout": "cp"},
    "sp+dots": {"layout": "sp", "remat_policy": "dots"},
    "cp+dots": {"layout": "cp", "remat_policy": "dots"},
    "tp+dots": {"remat_policy": "dots"},
    "cp+dots+kv": {"layout": "cp", "remat_policy": "dots+kv"},
    "sp+dots+kv": {"layout": "sp", "remat_policy": "dots+kv"},
    "sp+cf1": {"layout": "sp", "moe_overrides": {"capacity_factor": 1.0}},
    "sp+cf05": {"layout": "sp", "moe_overrides": {"capacity_factor": 0.5}},
    "cf1": {"moe_overrides": {"capacity_factor": 1.0}},
    "fsdp": {"layout": "fsdp"},
    "kv8": {"kv_quant": True},
    "fsdp+dots+kv+cf1": {"layout": "fsdp", "remat_policy": "dots+kv",
                         "moe_overrides": {"capacity_factor": 1.0}},
    "fsdp+cf1": {"layout": "fsdp", "moe_overrides": {"capacity_factor": 1.0}},
    "fsdp+dots+kv": {"layout": "fsdp", "remat_policy": "dots+kv"},
}


def run_variant(arch: str, shape_name: str, variant: str,
                note: str = "", out_dir: str = "results/perf") -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh()
    kw = VARIANTS[variant]
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "note": note, "ok": False}
    t0 = time.time()
    try:
        # full scanned compile: proof + memory analysis
        fn, kwargs = build_lowerable(cfg, shape, mesh, **kw)
        donate = ("cache",) if "cache" in kwargs else ()
        with jax.set_mesh(mesh):
            compiled = jax.jit(fn, donate_argnames=donate).lower(
                **kwargs).compile()
        if donate:
            rec["donated_cache"] = True
        mem = compiled.memory_analysis()
        rec["argument_size_in_bytes"] = int(mem.argument_size_in_bytes or 0)
        rec["output_size_in_bytes"] = int(mem.output_size_in_bytes or 0)
        rec["temp_size_in_bytes"] = int(mem.temp_size_in_bytes or 0)
        del compiled
        # exact per-device terms via unrolled depth differencing
        _, n_groups = cfg.layer_pattern()
        a1 = _analyse(_lower_compile(depth_variant(cfg, 1), shape, mesh,
                                     scan_layers=False, **kw))
        a2 = _analyse(_lower_compile(depth_variant(cfg, 2), shape, mesh,
                                     scan_layers=False, **kw))

        def extrap(x1, x2):
            per = max(x2 - x1, 0.0)
            return max(x1 - per, 0.0) + per * n_groups
        coll = {}
        for k in a1["collectives"]:
            if k == "total_bytes":
                continue
            coll[k] = {"bytes": int(extrap(a1["collectives"][k]["bytes"],
                                           a2["collectives"][k]["bytes"])),
                       "count": int(extrap(a1["collectives"][k]["count"],
                                           a2["collectives"][k]["count"]))}
        coll["total_bytes"] = sum(v["bytes"] for v in coll.values()
                                  if isinstance(v, dict))
        rec["hlo_flops"] = extrap(a1["flops"], a2["flops"])
        rec["hlo_bytes"] = extrap(a1["bytes"], a2["bytes"])
        rec["collectives"] = coll
        rec["roofline"] = roofline_report(rec, cfg, shape,
                                          n_chips=256)
        rec["ok"] = True
    except Exception as e:
        import traceback
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["total_s"] = round(time.time() - t0, 1)
    Path(out_dir).mkdir(parents=True, exist_ok=True)
    with open(Path(out_dir, f"{arch}__{shape_name}.jsonl"), "a") as f:
        f.write(json.dumps(rec, default=str) + "\n")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True, choices=list(VARIANTS))
    ap.add_argument("--note", default="")
    args = ap.parse_args()
    use_host_devices(512)
    rec = run_variant(args.arch, args.shape, args.variant, args.note)
    if rec["ok"]:
        ro = rec["roofline"]
        print(f"[OK] {args.arch} {args.shape} {args.variant}: "
              f"compute={ro['compute_s']*1e3:.1f}ms "
              f"memory={ro['memory_s']*1e3:.1f}ms "
              f"collective={ro['collective_s']*1e3:.1f}ms "
              f"dominant={ro['dominant']} "
              f"coll_GB={rec['collectives']['total_bytes']/1e9:.1f}")
    else:
        print(f"[FAIL] {rec.get('error')}\n{rec.get('traceback', '')[-1500:]}")


if __name__ == "__main__":
    main()
