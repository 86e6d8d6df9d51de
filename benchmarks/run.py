"""Benchmark driver.

Default mode runs one function per paper table/figure and prints
``name,case,value`` CSV lines (plus human-readable sections).

``--smoke`` is the CI gate (``bench-smoke`` job): a tiny CPU serving
benchmark (<5 min) whose results are written — schema-validated — to
``BENCH_serving.json`` (``--out`` overrides the path), alongside the
exported span trace of the observability leg (``--trace-out``, default
``BENCH_trace.json``; Chrome-trace JSON, schema-checked by
``validate_trace_doc``). The process exits non-zero when either
document is schema-invalid or empty, so perf numbers land in every CI
run or the gate fails loudly.

  PYTHONPATH=src python -m benchmarks.run [--csv]
  PYTHONPATH=src python -m benchmarks.run --smoke [--out BENCH_serving.json]
      [--trace-out BENCH_trace.json]

Field-by-field documentation of every ``metrics.*`` section in the
emitted document lives in docs/benchmarks.md.
"""

from __future__ import annotations

import json
import sys
import time


def smoke(out_path: str, trace_path: str = "BENCH_trace.json") -> None:
    import benchmarks.failover as failover
    import benchmarks.obs as obs
    import benchmarks.prefix_cache as prefix_cache
    import benchmarks.tiers as tiers
    import benchmarks.topology as topology
    import benchmarks.workload as workload
    from benchmarks.schema import validate_bench_serving, validate_trace_doc

    t0 = time.time()
    doc = prefix_cache.smoke()
    doc["metrics"]["net"] = topology.smoke()  # v3: non-uniform-topology
    #   run (per-link dispatch bytes, staged-migration transfer totals)
    doc["metrics"]["faults"] = failover.smoke()  # v5: mid-run crash +
    #   failover vs no-failover baseline, deterministic replay asserted
    doc["metrics"]["tiers"] = tiers.smoke()  # v6: oversized model over
    #   host-RAM expert tiers, prefetch vs frozen residency
    doc["metrics"]["workload"] = workload.smoke()  # v7: seeded flash-crowd
    #   stream, SLO-aware scheduling vs blind FIFO goodput on it
    doc["metrics"]["obs"] = obs.smoke(trace_out=trace_path)  # v8: traced
    #   faults+migration+tiers run, byte-identical replay, trace artifact
    doc["elapsed_s"] = round(time.time() - t0, 2)
    validate_bench_serving(doc)  # raises (non-zero exit) on breakage
    with open(trace_path) as f:
        validate_trace_doc(json.load(f))  # the uploaded trace artifact
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    m = doc["metrics"]
    print(
        f"wrote {out_path} in {doc['elapsed_s']}s: "
        f"chunk_reduction={m['prefill_chunk_reduction']:.2f}x "
        f"admitted {m['admitted_concurrency']['nocache']} -> "
        f"{m['admitted_concurrency']['cache']} "
        f"decode_round={m['decode_round_latency_s']['mean'] * 1e3:.1f}ms"
    )
    c = m["cluster"]
    print(
        f"cluster[v2]: {int(c['n_servers'])} servers "
        f"admitted={c['per_server_admitted']} "
        f"local_ratio={c['per_server_local_ratio']} "
        f"redirected={int(c['redirected_total'])}"
    )
    n = m["net"]
    print(
        f"net[v3]: cross_server={n['cross_server_bytes']:.3g}B "
        f"(uniform {n['cross_server_bytes_by_policy']['uniform']:.3g}B) "
        f"migrations={int(n['migrations_completed'])} "
        f"transfer={n['migration_transfer_seconds']:.3g}s "
        f"mem_gb={n['per_server_mem_gb']}"
    )
    p = m["perf"]
    print(
        f"perf[v4]: warmup={p['warmup_seconds']:.1f}s "
        f"({int(p['executables_compiled'])} executables) "
        f"retraces={int(p['traces_after_warmup'])} "
        f"stalls={int(p['host_syncs'])} "
        f"decode_round_ms p50={p['decode_round_ms']['p50']:.2f} "
        f"p99={p['decode_round_ms']['p99']:.2f} "
        f"ttft_ms p50={p['ttft_ms']['p50']:.2f}"
    )
    fl = m["faults"]
    print(
        f"faults[v5]: injected={int(fl['injected'])} "
        f"recovered={int(fl['recovered'])} "
        f"recovery={fl['recovery_seconds']:.3g}s "
        f"tokens_lost={int(fl['tokens_lost'])} "
        f"(baseline {int(fl['baseline_tokens_lost'])}) "
        f"replay_identical={int(fl['replay_identical'])}"
    )
    t = m["tiers"]
    print(
        f"tiers[v6]: gpu_slots={t['per_server_gpu_slots']} "
        f"promotions={int(t['promotions'])} "
        f"hit_ratio={t['prefetch_hit_ratio']:.3f} "
        f"stall={t['on_demand_stall_seconds']:.3g}s "
        f"(no-prefetch {t['prefetch_off_stall_seconds']:.3g}s) "
        f"latency={t['mean_latency_s']:.4f}s "
        f"(no-prefetch {t['prefetch_off_mean_latency_s']:.4f}s)"
    )
    w = m["workload"]
    print(
        f"workload[v7]: {int(w['requests'])} requests "
        f"goodput={w['goodput_tokens_per_s']:.1f}tok/s "
        f"(fifo {w['fifo_goodput_tokens_per_s']:.1f}) "
        f"attainment={w['slo_attainment']:.3f} "
        f"sheds={int(w['sheds'])} "
        f"flash_migrations={int(w['flash_migrations'])} "
        f"replay_identical={int(w['replay_identical'])}"
    )
    o = m["obs"]
    print(
        f"obs[v8]: {int(o['events'])} spans over "
        f"{len(o['span_counts'])} kinds "
        f"dropped={int(o['dropped_events'])} "
        f"overhead={o['overhead_ms']:.1f}ms "
        f"replay_identical={int(o['replay_identical'])} "
        f"trace={trace_path}"
    )


def main() -> None:
    if "--help" in sys.argv or "-h" in sys.argv:
        print(__doc__)
        return
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    if "--smoke" in sys.argv:
        out = "BENCH_serving.json"
        trace = "BENCH_trace.json"
        usage = (
            "usage: benchmarks.run --smoke [--out PATH] [--trace-out PATH]"
        )
        if "--out" in sys.argv:
            i = sys.argv.index("--out")
            if i + 1 >= len(sys.argv):
                sys.exit(usage)
            out = sys.argv[i + 1]
        if "--trace-out" in sys.argv:
            i = sys.argv.index("--trace-out")
            if i + 1 >= len(sys.argv):
                sys.exit(usage)
            trace = sys.argv[i + 1]
        smoke(out, trace)
        return

    import benchmarks.failover as failover
    import benchmarks.fig5 as fig5
    import benchmarks.obs as obs
    import benchmarks.fig6 as fig6
    import benchmarks.fig7 as fig7
    import benchmarks.fig8 as fig8
    import benchmarks.paged_pool as paged_pool
    import benchmarks.prefix_cache as prefix_cache
    import benchmarks.roofline_table as roofline_table
    import benchmarks.table1 as table1
    import benchmarks.table2 as table2
    import benchmarks.tiers as tiers
    import benchmarks.topology as topology
    import benchmarks.workload as workload

    csv = "--csv" in sys.argv
    for name, fn in [
        ("Table I  (offload vs collaboration)", table1.main),
        ("Table II (5 methods x 2 models x 2 workloads)", table2.main),
        ("Fig. 5   (latency vs remote fraction)", fig5.main),
        ("Fig. 6   (local compute ratio over time)", fig6.main),
        ("Fig. 7   (migration under workload shift)", fig7.main),
        ("Fig. 8   (scalability + bandwidth)", fig8.main),
        ("Roofline (single-pod dry-run)", roofline_table.main),
        ("Paged KV pool (occupancy + latency-vs-blocks)", paged_pool.main),
        ("Prefix cache (chunk reduction + concurrency)", prefix_cache.main),
        ("Topology  (non-uniform links, staged migration)", topology.main),
        ("Failover  (mid-run crash, recovery vs baseline)", failover.main),
        ("Tiers     (oversized model, host-RAM expert tiers)", tiers.main),
        ("Workload  (flash-crowd stream, SLO goodput)", workload.main),
        ("Obs       (unified tracing, byte-identical replay)", obs.main),
    ]:
        t0 = time.time()
        print(f"\n##### {name}")
        fn(csv=csv)
        print(f"##### done in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    main()
