"""The reduction from a profiler trace to device time, idle share,
collective share and the host span behind each idle gap."""

from pathlib import Path

import pytest

from bench import reduce_trace as R

HERE = Path(__file__).resolve().parent
NS = 1e-9


@pytest.fixture(scope="module")
def synthetic():
    """Two chips, three executable runs, host spans across the gaps."""
    return R.summarize(R.load_json(HERE / "trace_synthetic.json"))


def test_busy_and_idle(synthetic):
    assert synthetic.devices == 2
    assert synthetic.window_s == pytest.approx(500 * NS)
    assert synthetic.busy_s == pytest.approx(350 * NS)   # both chips alike


def test_per_executable_time(synthetic):
    assert synthetic.module_time(r"jit__dec\b") == (2, pytest.approx(200 * NS))
    assert synthetic.module_time(r"jit__chunk\b") == (1, pytest.approx(150 * NS))


def test_collective_share(synthetic):
    # all-reduce 40 + all-gather 20 on one chip, 50 + 10 on the other
    assert synthetic.collective_time(r"jit__dec\b") == pytest.approx(60 * NS)
    assert synthetic.collective_time(r"jit__chunk\b") == 0


def test_top_ops(synthetic):
    names = [n for n, _ in synthetic.top_ops]
    assert names == ["fusion.3", "fusion.1", "all-reduce.2", "all-gather.5"]
    assert synthetic.top_ops[1][1] == pytest.approx(140 * NS)


def test_gaps_named_by_host_spans(synthetic):
    assert synthetic.idle_gaps == [("bench.idle", pytest.approx(100 * NS)),
                                   ("bench.step", pytest.approx(50 * NS))]


def test_window_clips(synthetic):
    events = R.load_json(HERE / "trace_synthetic.json")
    s = R.summarize(events, window=(150, 300))
    assert s.busy_s == pytest.approx(150 * NS)
    assert s.module_time(r"jit__dec\b") == (0, 0)
    assert s.idle_gaps == []


def test_json_round_trip(tmp_path):
    events = R.load_json(HERE / "trace_synthetic.json")
    R.dump_json(events, tmp_path / "t.json.gz")
    assert R.load_json(tmp_path / "t.json.gz") == events


def test_xplane_without_a_chip(tmp_path):
    """A trace recorded here holds the host spans and no TPU plane, which
    the reduction refuses rather than report a device it never saw."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = R.load_xplane(R.find_xplane(tmp_path))
    assert any(e.name == "bench.step" for e in events)
    with pytest.raises(ValueError, match="no TPU device plane"):
        R.summarize(events)


@pytest.fixture(scope="module")
def recorded():
    """Three executable runs cut from a `mixtral-chat` trace on one v5e."""
    return R.load_json(HERE / "trace_v5e_mixtral.json.gz")


def test_recorded_trace(recorded):
    mods = [e for e in recorded if e.line == R.MODULES_LINE]
    lo = min(m.start for m in mods)
    hi = max(m.end for m in mods)
    s = R.summarize(recorded, window=(lo, hi))
    dec = [m.dur for m in mods if m.name.startswith("jit__dec(")]
    chunk = [m.dur for m in mods if m.name.startswith("jit__chunk(")]
    assert s.module_time(r"jit__dec\b") == (2, pytest.approx(sum(dec) * NS))
    assert s.module_time(r"jit__chunk\b") == (1, pytest.approx(sum(chunk) * NS))
    assert 0.99 * s.window_s < s.busy_s <= s.window_s    # a saturated chip
    assert s.collective_s == {}                          # one chip: none
    label, _ = s.top_ops[0]
    assert label.endswith("fusion") and "[8,1288," in label   # expert FFN
    assert all(not lab.endswith(" while") for lab, _ in s.top_ops)
    assert {g for g, _ in s.idle_gaps} <= {"bench.step", "bench.record",
                                           "bench.submit", "bench.idle",
                                           "host.other"}


def test_op_label():
    name = ("%fusion.313 = bf16[8,1288,14336]{2,1,0:T(8,128)(2,1)} "
            "fusion(bf16[8,1288,4096]{2,1,0} %bitcast.374), kind=kOutput")
    assert R.op_label(name) == ("%fusion.313 bf16[8,1288,14336] fusion", "fusion")
    loop = "%while.37 = (s32[]{:T(128)}, bf16[32,1,4096]{2,0,1}) while(%tuple.2)"
    assert R.op_label(loop) == ("%while.37 (tuple) while", "while")
