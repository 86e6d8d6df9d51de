"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Three terms per (arch x shape x mesh), all in seconds-per-step-per-chip:

  compute    = HLO_FLOPs / peak_FLOPs          (cost_analysis is per-device)
  memory     = HLO_bytes / HBM_bw
  collective = collective_bytes / ICI_bw

collective_bytes is NOT in cost_analysis: we parse the optimized HLO and sum
the result-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (all-reduce counted twice: reduce+broadcast
phases). This slightly over-counts a2a/ag by the local shard (factor
(n-1)/n) — a documented, placement-independent approximation; the
placement-aware *effective* a2a bytes (what DanceMoE actually optimises) are
reported separately by the perf harness using measured local fractions.
"""
from __future__ import annotations

import re


from repro.launch import mesh as mesh_lib

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?((?:[a-z0-9]+\[[^\]]*\](?:,\s*)?)+)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_LINE_RE = re.compile(
    r"^\s*\S+\s*=\s*(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(([^\n]*)", re.M)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo: str) -> dict:
    """Sum result-shape bytes per collective kind from optimized HLO text."""
    out = {k: {"bytes": 0, "count": 0}
           for k in ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")}
    for m in _LINE_RE.finditer(hlo):
        shape_str, kind, rest = m.group(1), m.group(2), m.group(4) or ""
        b = _shape_bytes(shape_str)
        if kind == "all-reduce":
            mult = 2              # reduce + broadcast phases
        elif kind == "reduce-scatter":
            # result is the post-scatter shard: moved bytes ~= input size
            g = _GROUPS_RE.search(rest)
            mult = int(g.group(2)) if g else 1
        else:
            mult = 1
        out[kind]["bytes"] += b * mult
        out[kind]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def model_flops(cfg, shape) -> float:
    """6·N_active·D per processed token (2·N·D for pure inference steps)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch      # decode: 1 token/seq


def roofline_report(rec: dict, cfg, shape, *, n_chips: int) -> dict:
    """Three-term roofline, per device per step.

    compute_s    — depth-differenced HLO FLOPs / peak (exact).
    memory_s     — structural HBM traffic: per-device argument bytes
                   (weights + optimizer state + KV cache, from
                   memory_analysis) x passes + outputs. This is the tight
                   bound; `memory_s_hlo` (per-op bytes accessed) is also
                   reported but double-counts fusion-internal operands.
    collective_s — parsed HLO collective bytes / ICI bw.
    """
    peak = mesh_lib.peaks(mesh_lib.TARGET_DEVICE_KIND)
    compute_t = rec["hlo_flops"] / peak["bf16_flops"]
    passes = 3.0 if shape.kind == "train" else 1.0
    out_bytes = rec.get("output_size_in_bytes", 0)
    if rec.get("donated_cache"):
        out_bytes = max(out_bytes - rec.get("argument_size_in_bytes", 0), 0)
    struct_bytes = (passes * rec.get("argument_size_in_bytes", 0)
                    + out_bytes)
    memory_t = struct_bytes / peak["hbm_bytes_per_s"]
    memory_t_hlo = rec["hlo_bytes"] / peak["hbm_bytes_per_s"]
    coll_bytes = rec["collectives"]["total_bytes"]
    collective_t = coll_bytes / peak["ici_link_bytes_per_s"]
    terms = {"compute_s": compute_t, "memory_s": memory_t,
             "collective_s": collective_t}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    useful = mf / max(rec["hlo_flops"] * n_chips, 1.0)
    return {
        **terms,
        "memory_s_hlo": memory_t_hlo,
        "dominant": dominant.removesuffix("_s"),
        "model_flops_global": mf,
        "useful_flops_ratio": useful,
        "step_time_lower_bound_s": max(terms.values()),
        "mfu_bound": mf / max(n_chips * peak["bf16_flops"]
                              * max(terms.values()), 1e-30),
    }
