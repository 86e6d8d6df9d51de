"""The program's spans, scopes, stamps and counters as the benchmark reads
them: a small recorded form with known values, and the traces and
records of a program that has none of them."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness
from bench import program_spans as PS
from bench import reduce_trace as R
from bench.context import Context

HERE = Path(__file__).resolve().parent
NEW = ("decode_expert_ffn_ms", "decode_attention_ms", "host_tick_self_ms",
       "prefill_tokens_per_call", "prefill_wait_p90_ms")


@pytest.fixture(scope="module")
def recorded():
    return PS.load_json(HERE / "trace_program_synthetic.json")


@pytest.fixture(scope="module")
def trace(recorded):
    trace, hlo = recorded
    return PS.name_ops(trace, [PS.hlo_op_names(t) for t in hlo])


def _handle(**wall):
    return SimpleNamespace(wall=wall)


def _records():
    return [SimpleNamespace(handle=h) for h in (
        _handle(admitted=1.5, first_token=2.5),   # 1.0
        _handle(admitted=0.5, first_token=1.2),   # admitted before the window
        _handle(admitted=3.0),                    # still prefilling: 2.0
        _handle(),                                # still queued
        None,                                     # refused at submission
        _handle(admitted=2.0, first_token=6.0),   # first token after: 3.0
    )]


def test_scope_of():
    assert PS.scope_of("jit(_dec)/while/body/shard_map/moe.expert_ffn/dot"
                       ) == "moe.expert_ffn"
    assert PS.scope_of("jit(_dec)/attn/head/x") == "head"     # innermost
    assert PS.scope_of("jit(_dec)/attention/dot") is None
    assert PS.scope_of("") is None


def test_hlo_op_names(recorded):
    _, (dec4, dec8, _) = recorded
    names = PS.hlo_op_names(dec8)
    assert names["%fusion.2 bf16[8,16,64] fusion"].endswith(
        "/moe.expert_ffn/dot_general")
    assert names["%copy.5 s32[9] copy"] == ""        # no metadata
    assert "%dot.9 bf16[8,1,64] dot" in names       # fused computations too
    # the executable of another width labels one operation alike, under
    # another scope: runs are matched to the executable holding all they ran
    assert PS.scope_of(PS.hlo_op_names(dec4)[
        "%fusion.2 bf16[8,16,64] fusion"]) == "attn"


def test_scope_times(trace):
    ms, share = PS.scope_times(trace, PS.STEPS["decode"])
    assert ms == pytest.approx({"attn": 35.0, "moe.expert_ffn": 50.0,
                                "head": 5.0})
    assert share == pytest.approx(0.9)        # the copy carries no scope
    ms, share = PS.scope_times(trace, PS.STEPS["chunk"])
    assert ms == pytest.approx({"moe.expert_ffn": 150.0})
    assert share == pytest.approx(1.0)


def test_host_spans(trace):
    assert {s.name for s in trace.host} == {
        f"serve.{k}" for k in ("tick", "admit", "prefill", "decode",
                               "drain", "wait", "review", "migrate")}
    assert PS.tick_self_ms(trace) == pytest.approx([70.0, 30.0])
    assert PS.named_gaps(trace) == [("serve.decode", pytest.approx(0.1)),
                                    ("serve.migrate", pytest.approx(0.05))]


def test_prefill_waits_and_counters():
    assert PS.prefill_waits(_records(), 1.0, 5.0) == pytest.approx(
        [1.0, 2.0, 3.0])
    old = [SimpleNamespace(handle=SimpleNamespace(admitted_at=1))]
    assert PS.prefill_waits(old, 1.0, 5.0) is None
    runtimes = [SimpleNamespace(prefill_tokens=3, prefill_calls=1,
                                ep_rows_dropped=0)] * 2
    assert PS.counters(runtimes) == {"prefill_tokens": 6,
                                     "prefill_calls": 2,
                                     "ep_rows_dropped": 0}
    older = [SimpleNamespace(prefill_calls=4)]
    c = PS.counters(older)
    assert c == {"prefill_tokens": None, "prefill_calls": 4,
                 "ep_rows_dropped": None}
    assert PS.deltas(c, c)["prefill_calls"] == 0
    assert PS.deltas(c, c)["prefill_tokens"] is None


def test_readers_by_hand():
    c0 = {"prefill_tokens": 100, "prefill_calls": 10, "ep_rows_dropped": 0}
    c1 = {"prefill_tokens": 340, "prefill_calls": 14, "ep_rows_dropped": 0}
    trace, hlo = PS.load_json(HERE / "trace_program_synthetic.json")
    ctx = SimpleNamespace(program=PS.reading(trace, _records(), 1.0, 5.0,
                                             c0, c1, hlo))
    read = {n: harness.metric_reader(n)(ctx) for n in NEW}
    assert read == pytest.approx({
        "decode_expert_ffn_ms": 50.0, "decode_attention_ms": 35.0,
        "host_tick_self_ms": 50.0, "prefill_tokens_per_call": 60.0,
        "prefill_wait_p90_ms": 2800.0})


def test_scope_rooflines_by_hand():
    """A scope's roofline: its needed work per call (the mean over the
    recorded calls) over its device time per run of the step."""
    trace, hlo = PS.load_json(HERE / "trace_program_synthetic.json")
    program = PS.reading(trace, hlo=hlo)     # attn 35 ms, expert FFN 50 ms
    calls = [{"step": {"flops": 0.0, "bytes": 0.0},
              "attn": {"flops": f, "bytes": 1e6},
              "moe.expert_ffn": {"flops": 1e9, "bytes": b}}
             for f, b in ((1e9, 2e7), (3e9, 4e7))]
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = Context(window=None, counters={}, chips=1, peaks=peaks,
                  work={"decode": calls, "chunk": []}, program=program)
    read = {n: harness.metric_reader(n)(ctx) for n in (
        "decode_attention_roofline", "decode_expert_ffn_roofline")}
    assert read["decode_attention_roofline"] == {
        "value": pytest.approx(100 * 2e-3 / 35e-3), "bound": "flops"}
    assert read["decode_expert_ffn_roofline"] == {
        "value": pytest.approx(100 * 30e-3 / 50e-3), "bound": "hbm"}
    for ctx in (Context(window=None, counters={}, chips=1, peaks=peaks,
                        work={"decode": calls}),
                Context(window=None, counters={}, chips=1, peaks=peaks,
                        program=program)):
        assert harness.metric_reader("decode_attention_roofline")(ctx) is None


@pytest.mark.parametrize("name,before", [
    ("trace_synthetic.json", (1e-4, 1.5e-4, 30.0)),
    ("trace_v5e_mixtral.json.gz", (65.732333, 45.996855, 0.0039872091)),
])
def test_a_program_without_spans_reads_nothing(name, before):
    """The traces the benchmark already keeps hold no ``serve.*`` span and
    no scoped operation: every new reading is empty, every new reader
    None, and the readers already there read what they read before."""
    trace, hlo = PS.load_json(HERE / name)
    assert trace.host == [] and trace.ops and hlo == []
    r = PS.reading(trace, [SimpleNamespace(handle=None)], 0.0, 1.0,
                   PS.counters([SimpleNamespace()]),
                   PS.counters([SimpleNamespace()]))
    assert r.scope_ms == {"decode": {}, "chunk": {}}
    assert r.tick_self_ms == [] and r.prefill_waits is None
    assert {g for g, _ in r.gaps} == {"host.other"}
    for ctx in (SimpleNamespace(program=r), SimpleNamespace()):
        assert all(harness.metric_reader(n)(ctx) is None for n in NEW)
    s = R.summarize(R.load_json(HERE / name))
    ctx = Context(window=None, counters={}, chips=s.devices, peaks=None,
                  trace=s)
    old = tuple(harness.metric_reader(n)(ctx) for n in (
        "decode_step_ms", "prefill_chunk_ms", "device_idle_share"))
    assert old == pytest.approx(before)


def test_reduce_trace_reads_the_program_trace():
    """The program's recorded form is the benchmark's trace form: the
    reduction already there reads it as it reads any trace."""
    s = R.summarize(R.load_json(HERE / "trace_program_synthetic.json"))
    assert s.module_time(Context.DECODE) == (2, pytest.approx(0.2))
    assert s.busy_s == pytest.approx(0.35) and s.window_s == pytest.approx(0.5)
    assert [g for g, _ in s.idle_gaps] == ["bench.idle", "bench.step"]


def test_xplane_host_spans(tmp_path):
    """The program's host spans as the profiler writes them here: no
    device plane, so no operation is read."""
    import jax

    from repro.serving.obs import SpanKind, host_span

    jax.profiler.start_trace(str(tmp_path))
    with host_span(SpanKind.TICK):
        with host_span(SpanKind.DRAIN):
            with host_span(SpanKind.WAIT):
                jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    trace = PS.load_xplane(R.find_xplane(tmp_path))
    assert [s.name for s in sorted(trace.host, key=lambda s: s.start)] == [
        "serve.tick", "serve.drain", "serve.wait"]
    assert trace.ops == [] and trace.modules == []
    (self_ms,) = PS.tick_self_ms(trace)
    tick, _, wait = sorted(trace.host, key=lambda s: s.start)
    assert self_ms == pytest.approx((tick.dur - wait.dur) * 1e-6)
