"""Schema contract for the serving benchmark trajectory
(``BENCH_serving.json``, produced by ``benchmarks/run.py --smoke`` and
gated by the CI ``bench-smoke`` job).

The document is intentionally small and versioned: every CI run uploads
one, so schema breaks show up as a failed gate — not as a silently empty
perf history. Validation is dependency-free (no jsonschema install on the
runner)."""

from __future__ import annotations

SCHEMA_NAME = "bench-serving/v9"

# metric key -> ("scalar" | "pair" | "stats") shape requirement.
# v2 extended v1 (same keys, same shapes) with the EdgeCluster section;
# v3 adds the heterogeneous-topology section (``metrics.net``) and the
# per-server profile caps; v4 adds the AOT warmup / zero-stall section
# (``metrics.perf``); v5 adds the fault-injection/failover section
# (``metrics.faults``); v6 adds the expert tier hierarchy section
# (``metrics.tiers``); v7 adds the streaming-workload / SLO-scheduling
# section (``metrics.workload``); v8 adds the unified-observability
# section (``metrics.obs``) and the exported-trace artifact contract
# (``validate_trace_doc``); v9 adds ``metrics.perf.ep_rows_dropped`` —
# extend, don't fork, when adding serving metrics.
# Field-by-field documentation: docs/benchmarks.md.
_REQUIRED_METRICS = {
    "admitted_concurrency": "pair",  # {"cache": n, "nocache": n}
    "prefill_chunks_executed": "pair",
    "prefill_chunk_reduction": "scalar",
    "prefix_hits": "scalar",
    "prefill_tokens_skipped": "scalar",
    "cow_copies": "scalar",
    "deferrals": "pair",
    "decode_round_latency_s": "stats",  # {"mean": s, "p95": s}
    "mean_latency_ticks": "pair",
}

# v2: metrics.cluster — per-server serving metrics emitted by an
# EdgeCluster run ("list" = per-server list of n_servers numbers).
# v3 adds the heterogeneous profile caps each server ran under.
_REQUIRED_CLUSTER = {
    "n_servers": "scalar",
    "per_server_admitted": "list",  # requests admitted per origin
    "per_server_routed": "list",  # requests routed to each server
    "per_server_local_ratio": "list",  # local-compute ratio in [0, 1]
    "redirected_total": "scalar",  # requests served off-origin
    "per_server_mem_gb": "list",  # heterogeneous memory caps
}

# v3: metrics.net — the topology/communication section produced by
# ``benchmarks.topology`` (non-uniform 3-server topology, link-aware
# controller, staged migration). "matrix" = [n_servers][n_servers]
# non-negative numbers.
_REQUIRED_NET = {
    "n_servers": "scalar",
    "link_dispatch_bytes": "matrix",  # per-(src, dst) dispatch bytes
    "cross_server_bytes": "scalar",
    "migration_transfer_seconds": "scalar",  # staged-migration link time
    "migration_transfer_bytes": "scalar",
    "migrations_completed": "scalar",
    "per_server_mem_gb": "list",
    "per_server_expert_budget": "list",
}

# v4: metrics.perf — AOT bucket-ladder warmup + zero-stall decode loop
# ("p50p99" = {"p50": ms, "p99": ms}). Produced by the warmed serving leg
# of ``benchmarks.prefix_cache``.
_REQUIRED_PERF = {
    "warmup_seconds": "scalar",  # wall time of the AOT compile pass
    "executables_compiled": "scalar",  # bucket-ladder size
    "traces_after_warmup": "scalar",  # jit retraces past warmup (want 0)
    "host_syncs": "scalar",  # blocking host waits (stall count)
    "rounds_timed": "scalar",  # decode rounds behind the percentiles
    "decode_round_ms": "p50p99",  # per-round wall time, warmed loop
    "ttft_ms": "p50p99",  # wall-clock time to first token
    "ep_rows_dropped": "scalar",  # EP capacity drops (v9; dropless: 0)
}


# v5: metrics.faults — the deterministic fault-injection/failover section
# produced by ``benchmarks.failover`` (3-server WAN topology, mid-run
# crash of the memory-poor server, failover vs crash-oblivious baseline).
_REQUIRED_FAULTS = {
    "injected": "scalar",  # fault events consumed from the schedule
    "recovered": "scalar",  # crashes whose recovery review was adopted
    "tokens_lost": "scalar",  # failover leg (want 0)
    "recovery_seconds": "scalar",  # crash -> recovery-migration eta
    "requests_dropped": "scalar",  # failover leg (want 0)
    "baseline_tokens_lost": "scalar",  # no-failover comparison
    "baseline_requests_dropped": "scalar",
    "replay_identical": "scalar",  # 1 iff reruns were bit-identical
}


# v6: metrics.tiers — the expert tier hierarchy / oversized-model section
# produced by ``benchmarks.tiers`` (aggregate expert set > aggregate GPU
# memory; host-RAM tiers behind each GPU; activation-aware prefetch vs a
# frozen-residency baseline).
_REQUIRED_TIERS = {
    "n_servers": "scalar",
    "per_server_gpu_slots": "list",  # GPU-tier expert slots (whole server)
    "per_server_host_slots": "list",  # deepest-tier slots (cumulative)
    "per_server_gpu_resident": "list",  # experts GPU-resident at run end
    "per_server_host_resident": "list",  # experts parked in back tiers
    "promotions": "scalar",  # host->GPU prefetch fetches that landed
    "demotions": "scalar",  # GPU->back-tier moves (free: inclusive tiers)
    "prefetch_hit_ratio": "scalar",  # GPU-resident activation fraction
    "on_demand_fetches": "scalar",  # cold-expert fetch events
    "on_demand_stall_seconds": "scalar",  # modeled stall total
    "mean_latency_s": "scalar",  # prefetch leg, modeled seconds
    "prefetch_off_mean_latency_s": "scalar",  # frozen-residency baseline
    "prefetch_off_fetches": "scalar",
    "prefetch_off_stall_seconds": "scalar",
}


# v7: metrics.workload — the streaming-workload / SLO-aware-scheduling
# goodput section produced by ``benchmarks.workload`` (seeded flash-crowd
# stream over the WAN testbed; SLO-aware vs FIFO legs on the same stream;
# "p50p99" = {"p50": s, "p99": s}). ``phases`` is validated separately:
# a non-empty {phase: stats} object.
_REQUIRED_WORKLOAD = {
    "n_servers": "scalar",
    "requests": "scalar",  # stream length both legs consumed
    "sheds": "scalar",  # SLO-aware leg's shed count (gated >= 1)
    "deadline_redirects": "scalar",  # served off-route to make the SLO
    "flash_migrations": "scalar",  # migrations completed at/after crowd
    "goodput_tokens_per_s": "scalar",  # SLO-attained tokens / modeled s
    "fifo_goodput_tokens_per_s": "scalar",  # blind-FIFO baseline leg
    "slo_attainment": "scalar",  # fraction of SLO'd requests that met it
    "fifo_slo_attainment": "scalar",
    "ttft_s": "p50p99",  # modeled time-to-first-token, SLO-aware leg
    "itl_s": "p50p99",  # modeled inter-token latency
    "replay_identical": "scalar",  # 1 iff the rerun was bit-identical
}


# v8: metrics.obs — the unified-tracing section produced by
# ``benchmarks.obs`` (one sim run stacking faults + staged migration +
# tier prefetch, traced, exported and byte-compared against its rerun).
# ``clock`` ("ticks" | "seconds") and ``span_counts`` (non-empty
# {kind: count} object) are validated separately.
_REQUIRED_OBS = {
    "enabled": "scalar",  # 1 iff the tracer recorded (gated == 1)
    "events": "scalar",  # spans retained (gated >= 1)
    "dropped_events": "scalar",  # spans past max_events (gated == 0)
    "overhead_ms": "scalar",  # wall cost of recording (replay-exempt)
    "replay_identical": "scalar",  # 1 iff trace reruns byte-identical
}


class BenchSchemaError(ValueError):
    """Raised when a BENCH_serving.json document violates the contract."""


def _num(doc: dict, path: str, key: str) -> float:
    v = doc.get(key)
    if not isinstance(v, (int, float)) or isinstance(v, bool):
        raise BenchSchemaError(f"{path}.{key}: expected a number, got {v!r}")
    if v < 0:
        raise BenchSchemaError(f"{path}.{key}: negative value {v!r}")
    return v


def validate_bench_serving(doc) -> dict:
    """Validate a BENCH_serving.json document; returns it on success,
    raises ``BenchSchemaError`` on a missing/mis-typed/empty field."""
    if not isinstance(doc, dict) or not doc:
        raise BenchSchemaError("document must be a non-empty JSON object")
    if doc.get("schema") != SCHEMA_NAME:
        raise BenchSchemaError(
            f"schema: expected {SCHEMA_NAME!r}, got {doc.get('schema')!r}"
        )
    if doc.get("mode") not in ("smoke", "full"):
        raise BenchSchemaError(f"mode: invalid {doc.get('mode')!r}")
    metrics = doc.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        raise BenchSchemaError("metrics: missing or empty")
    for key, kind in _REQUIRED_METRICS.items():
        if key not in metrics:
            raise BenchSchemaError(f"metrics.{key}: missing")
        if kind == "scalar":
            _num(metrics, "metrics", key)
            continue
        sub = metrics[key]
        if not isinstance(sub, dict):
            raise BenchSchemaError(f"metrics.{key}: expected an object")
        fields = ("cache", "nocache") if kind == "pair" else ("mean", "p95")
        for f in fields:
            if f not in sub:
                raise BenchSchemaError(f"metrics.{key}.{f}: missing")
            _num(sub, f"metrics.{key}", f)
    # an all-zero serving run means the benchmark didn't actually serve
    if (
        metrics["admitted_concurrency"]["cache"] < 1
        or metrics["prefill_chunks_executed"]["nocache"] < 1
    ):
        raise BenchSchemaError("metrics: empty run (nothing was served)")

    # -- v2: the EdgeCluster per-server section ---------------------------
    cluster = metrics.get("cluster")
    if not isinstance(cluster, dict) or not cluster:
        raise BenchSchemaError("metrics.cluster: missing or empty (v2)")
    _validate_section(cluster, "metrics.cluster", _REQUIRED_CLUSTER)
    if any(x > 1.0 for x in cluster["per_server_local_ratio"]):
        raise BenchSchemaError(
            "metrics.cluster.per_server_local_ratio: ratio > 1"
        )
    if sum(cluster["per_server_admitted"]) < 1:
        raise BenchSchemaError(
            "metrics.cluster: empty cluster run (nothing was served)"
        )

    # -- v3: the topology/communication section ---------------------------
    net = metrics.get("net")
    if not isinstance(net, dict) or not net:
        raise BenchSchemaError("metrics.net: missing or empty (v3)")
    _validate_section(net, "metrics.net", _REQUIRED_NET)
    if net["cross_server_bytes"] <= 0:
        raise BenchSchemaError(
            "metrics.net.cross_server_bytes: empty run (no dispatch "
            "traffic was metered)"
        )

    # -- v4: the AOT warmup / zero-stall perf section ---------------------
    perf = metrics.get("perf")
    if not isinstance(perf, dict) or not perf:
        raise BenchSchemaError("metrics.perf: missing or empty (v4)")
    for key, kind in _REQUIRED_PERF.items():
        if key not in perf:
            raise BenchSchemaError(f"metrics.perf.{key}: missing")
        if kind == "scalar":
            _num(perf, "metrics.perf", key)
            continue
        sub = perf[key]
        if not isinstance(sub, dict):
            raise BenchSchemaError(f"metrics.perf.{key}: expected an object")
        for f in ("p50", "p99"):
            if f not in sub:
                raise BenchSchemaError(f"metrics.perf.{key}.{f}: missing")
            _num(sub, f"metrics.perf.{key}", f)
    # an unwarmed or idle perf section means the warmed leg didn't run
    if perf["executables_compiled"] < 1:
        raise BenchSchemaError(
            "metrics.perf.executables_compiled: empty (no AOT warmup ran)"
        )
    if perf["decode_round_ms"]["p50"] <= 0 or perf["rounds_timed"] < 1:
        raise BenchSchemaError(
            "metrics.perf.decode_round_ms: empty (no decode rounds timed)"
        )

    # -- v5: the fault-injection / failover section -----------------------
    faults = metrics.get("faults")
    if not isinstance(faults, dict) or not faults:
        raise BenchSchemaError("metrics.faults: missing or empty (v5)")
    for key in _REQUIRED_FAULTS:
        if key not in faults:
            raise BenchSchemaError(f"metrics.faults.{key}: missing")
        _num(faults, "metrics.faults", key)
    if faults["injected"] < 1:
        raise BenchSchemaError(
            "metrics.faults.injected: empty run (no fault was injected)"
        )
    if faults["replay_identical"] != 1:
        raise BenchSchemaError(
            "metrics.faults.replay_identical: fault replay was not "
            "bit-identical"
        )

    # -- v6: the expert tier hierarchy / oversized-model section ----------
    tiers = metrics.get("tiers")
    if not isinstance(tiers, dict) or not tiers:
        raise BenchSchemaError("metrics.tiers: missing or empty (v6)")
    _validate_section(tiers, "metrics.tiers", _REQUIRED_TIERS)
    if tiers["promotions"] < 1:
        raise BenchSchemaError(
            "metrics.tiers.promotions: empty run (the prefetcher never "
            "promoted an expert)"
        )
    if tiers["prefetch_hit_ratio"] > 1.0:
        raise BenchSchemaError("metrics.tiers.prefetch_hit_ratio: ratio > 1")

    # -- v7: the streaming-workload / SLO-scheduling section --------------
    wl = metrics.get("workload")
    if not isinstance(wl, dict) or not wl:
        raise BenchSchemaError("metrics.workload: missing or empty (v7)")
    for key, kind in _REQUIRED_WORKLOAD.items():
        if key not in wl:
            raise BenchSchemaError(f"metrics.workload.{key}: missing")
        if kind == "scalar":
            _num(wl, "metrics.workload", key)
            continue
        sub = wl[key]
        if not isinstance(sub, dict):
            raise BenchSchemaError(
                f"metrics.workload.{key}: expected an object"
            )
        for f in ("p50", "p99"):
            if f not in sub:
                raise BenchSchemaError(f"metrics.workload.{key}.{f}: missing")
            _num(sub, f"metrics.workload.{key}", f)
    phases = wl.get("phases")
    if not isinstance(phases, dict) or not phases:
        raise BenchSchemaError(
            "metrics.workload.phases: missing or empty (v7)"
        )
    if wl["requests"] < 1:
        raise BenchSchemaError(
            "metrics.workload.requests: empty run (no stream was served)"
        )
    for key in ("slo_attainment", "fifo_slo_attainment"):
        if wl[key] > 1.0:
            raise BenchSchemaError(f"metrics.workload.{key}: ratio > 1")
    if wl["replay_identical"] != 1:
        raise BenchSchemaError(
            "metrics.workload.replay_identical: the seeded stream rerun "
            "was not bit-identical"
        )
    if wl["goodput_tokens_per_s"] <= wl["fifo_goodput_tokens_per_s"]:
        raise BenchSchemaError(
            "metrics.workload: SLO-aware goodput did not beat the FIFO "
            "baseline — the scheduling gate regressed"
        )

    # -- v8: the unified-observability / tracing section ------------------
    obs = metrics.get("obs")
    if not isinstance(obs, dict) or not obs:
        raise BenchSchemaError("metrics.obs: missing or empty (v8)")
    for key in _REQUIRED_OBS:
        if key not in obs:
            raise BenchSchemaError(f"metrics.obs.{key}: missing")
        _num(obs, "metrics.obs", key)
    if obs.get("clock") not in ("ticks", "seconds"):
        raise BenchSchemaError(
            f"metrics.obs.clock: expected 'ticks' or 'seconds', got "
            f"{obs.get('clock')!r}"
        )
    counts = obs.get("span_counts")
    if not isinstance(counts, dict) or not counts:
        raise BenchSchemaError("metrics.obs.span_counts: missing or empty")
    for kind, n in counts.items():
        if not isinstance(n, int) or isinstance(n, bool) or n < 1:
            raise BenchSchemaError(
                f"metrics.obs.span_counts.{kind}: invalid count {n!r}"
            )
    if obs["enabled"] != 1 or obs["events"] < 1:
        raise BenchSchemaError(
            "metrics.obs: empty run (the tracer recorded nothing)"
        )
    if obs["dropped_events"] != 0:
        raise BenchSchemaError(
            f"metrics.obs.dropped_events: {obs['dropped_events']} spans "
            "were dropped at the max_events cap"
        )
    if obs["replay_identical"] != 1:
        raise BenchSchemaError(
            "metrics.obs.replay_identical: the traced rerun did not "
            "export byte-identical JSON"
        )
    return doc


def validate_trace_doc(doc) -> dict:
    """Validate an exported Chrome-trace document (the ``bench-smoke``
    trace artifact, written by ``Tracer.export``); returns it on
    success, raises ``BenchSchemaError``. Deliberately self-contained —
    no ``repro`` import, so the CI gate stays dependency-free."""
    if not isinstance(doc, dict) or not doc:
        raise BenchSchemaError("trace: document must be a non-empty object")
    other = doc.get("otherData")
    if not isinstance(other, dict):
        raise BenchSchemaError("trace.otherData: missing")
    if other.get("clock") not in ("ticks", "seconds"):
        raise BenchSchemaError(
            f"trace.otherData.clock: invalid {other.get('clock')!r}"
        )
    if other.get("dropped") != 0:
        raise BenchSchemaError(
            f"trace.otherData.dropped: {other.get('dropped')!r} events "
            "were dropped"
        )
    events = doc.get("traceEvents")
    if not isinstance(events, list) or not events:
        raise BenchSchemaError("trace.traceEvents: missing or empty")
    n_spans = 0
    for i, e in enumerate(events):
        if not isinstance(e, dict) or e.get("ph") not in ("X", "M"):
            raise BenchSchemaError(f"trace.traceEvents[{i}]: invalid {e!r}")
        if e["ph"] == "M":
            continue
        n_spans += 1
        for key in ("name", "cat", "pid", "tid", "ts", "dur", "args"):
            if key not in e:
                raise BenchSchemaError(f"trace.traceEvents[{i}].{key}: missing")
        for key in ("ts", "dur"):
            v = e[key]
            if not isinstance(v, (int, float)) or isinstance(v, bool) or v < 0:
                raise BenchSchemaError(
                    f"trace.traceEvents[{i}].{key}: invalid {v!r}"
                )
        if not isinstance(e["args"], dict) or "seq" not in e["args"]:
            raise BenchSchemaError(
                f"trace.traceEvents[{i}].args: missing the seq stamp"
            )
    if n_spans < 1:
        raise BenchSchemaError("trace: no complete ('X') events")
    if other.get("spans") != n_spans:
        raise BenchSchemaError(
            f"trace.otherData.spans: {other.get('spans')!r} != {n_spans} "
            "counted events"
        )
    return doc


def _validate_section(sec: dict, path: str, required: dict) -> None:
    """Shared per-server section validation: ``n_servers`` sizes every
    "list" (length n) and "matrix" (n x n, non-negative) entry."""
    n = _num(sec, path, "n_servers")
    if n < 1 or n != int(n):
        raise BenchSchemaError(f"{path}.n_servers: invalid {n!r}")
    n = int(n)

    def check_row(v, key, length):
        if not isinstance(v, list) or len(v) != length:
            raise BenchSchemaError(
                f"{path}.{key}: expected a list of {length} numbers, "
                f"got {v!r}"
            )
        for i, x in enumerate(v):
            if not isinstance(x, (int, float)) or isinstance(x, bool) or x < 0:
                raise BenchSchemaError(f"{path}.{key}[{i}]: invalid {x!r}")

    for key, kind in required.items():
        if key not in sec:
            raise BenchSchemaError(f"{path}.{key}: missing")
        if kind == "scalar":
            _num(sec, path, key)
        elif kind == "list":
            check_row(sec[key], key, n)
        elif kind == "matrix":
            rows = sec[key]
            if not isinstance(rows, list) or len(rows) != n:
                raise BenchSchemaError(
                    f"{path}.{key}: expected {n} rows, got {rows!r}"
                )
            for r, row in enumerate(rows):
                check_row(row, f"{key}[{r}]", n)
