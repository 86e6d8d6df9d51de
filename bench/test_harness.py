"""CPU rehearsal of the harness: the generator, the metric arithmetic, the
files ``BENCHMARK.json`` names, and the refusal to run without a chip."""

import json
import os
import shutil
import subprocess
import sys
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest

from bench import generator, harness, stats
from bench.openloop import Record

ROOT = harness.ROOT


def _traffic():
    return json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())


def test_generator_replays_from_seed():
    t = _traffic()
    a = generator.stream(t, 2**33 + 7, 20.0, 40.0, 32000)
    b = generator.stream(t, 2**33 + 7, 20.0, 40.0, 32000)
    assert len(a) == len(b) == round(t["rate_per_s"] * 20) + round(
        t["rate_per_s"] * 40)
    for x, y in zip(a, b):
        assert x.at == y.at and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)


def test_seeds_reorder_one_set_of_sizes():
    """Every seed offers each segment the traffic file's one set of gaps
    and lengths, so the window's work does not depend on the seed."""
    t = _traffic()
    runs = [generator.stream(t, seed, 20.0, 40.0, 32000) for seed in (1, 2)]
    for seg, (lo, hi) in enumerate(((0.0, 20.0), (20.0, 60.0))):
        fs = generator.fixed_set(t, hi - lo, seg)
        for reqs in runs:
            inside = [x for x in reqs if lo <= x.at < hi]
            assert Counter((len(x.prompt), x.max_new_tokens) for x in inside) \
                == Counter(zip(fs["prompts"], fs["outputs"]))
            gaps = np.diff([lo] + [x.at for x in inside])
            assert set(np.round(gaps, 6)) <= set(np.round(fs["gaps"], 6))
    lo, hi = t["prompt_len"][2:]
    assert all(lo <= len(x.prompt) <= hi for x in runs[0])


@pytest.mark.parametrize("origins", [1, 4])
def test_every_seed_offers_one_timeline(origins):
    """Two seeds give the same arrival times, prompt and output lengths
    and origins, in the same order, and other tokens: the seed draws the
    tokens alone."""
    t = {**_traffic(), "origins": origins, "origin_skew": 1.0,
         "origin_tokens": {"zipf": 1.1} if origins > 1 else None}
    a, b = (generator.stream(t, seed, 20.0, 51.0, 32000)
            for seed in (2**40 + 3, 8000001601))
    assert len(a) == len(b) > 100
    assert [(x.at, len(x.prompt), x.max_new_tokens, x.origin) for x in a] \
        == [(x.at, len(x.prompt), x.max_new_tokens, x.origin) for x in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_origins_draw_their_own_tokens():
    t = {**_traffic(), "origins": 4, "origin_skew": 1.0,
         "origin_tokens": {"zipf": 1.1}}
    reqs = generator.stream(t, 5, 50.0, 150.0, 32000)
    by = {o: np.concatenate([r.prompt for r in reqs if r.origin == o])
          for o in range(4)}
    top = {o: np.bincount(v, minlength=32000).argmax() for o, v in by.items()}
    assert len(set(top.values())) == 4          # each origin its own mode
    counts = np.bincount([r.origin for r in reqs], minlength=4)
    assert counts[0] > counts[3]                # Zipf skew over origins


def _rec(due, submitted, admitted, tokens, prompt=10):
    return Record(due=due, prompt_len=prompt, max_new_tokens=len(tokens),
                  origin=None, submitted=submitted, admitted=admitted,
                  token_times=list(tokens))


def test_window_arithmetic_by_hand():
    recs = [
        _rec(0.5, 0.6, 0.7, [1.5, 2.0, 2.25]),       # due before the window
        _rec(1.0, 1.1, 1.2, [2.0, 2.5, 3.5]),
        _rec(2.0, 2.0, 2.5, [4.0, 6.0]),             # second token after end
        _rec(4.0, 4.3, float("nan"), []),            # still queued at the end
        _rec(5.5, 5.5, float("nan"), []),            # due after the end
    ]
    w = stats.window(recs, 1.0, 5.0)
    assert w.attempted == 3 and w.failed == 0
    assert w.ttft == pytest.approx([1.0, 2.0, 1.0])  # 2.0-1.0, 4.0-2.0, 5.0-4.0
    assert w.gaps == pytest.approx([0.5, 0.25, 0.5, 1.0])
    assert w.tokens == 7
    assert w.submit_lag == pytest.approx([0.1, 0.0, 0.3])
    assert w.queue_wait == pytest.approx([0.2, 0.5, 1.0])
    assert w.prompt_tokens_admitted == 20
    assert stats.percentile(w.ttft, 90) == pytest.approx(1.8)
    assert stats.percentile([1, 2, 3, 4], 95) == pytest.approx(3.85)
    ctx = SimpleNamespace(window=w, counters={"prefix_tokens_skipped": 5})
    read = {n: harness.metric_reader(n) for n in (
        "ttft_p90_ms", "itl_p95_ms", "output_tok_s", "queue_wait_p90_ms",
        "submit_lag_p99_ms", "prefix_hit_share")}
    assert read["ttft_p90_ms"](ctx) == pytest.approx(1800.0)
    assert read["itl_p95_ms"](ctx) == pytest.approx(925.0)
    assert read["output_tok_s"](ctx) == pytest.approx(7 / 4)
    assert read["queue_wait_p90_ms"](ctx) == pytest.approx(900.0)
    assert read["submit_lag_p99_ms"](ctx) == pytest.approx(296.0)
    assert read["prefix_hit_share"](ctx) == pytest.approx(25.0)


def test_benchmark_names_only_files_that_exist():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bm["end_to_end"] + bm["per_layer"]}
    for n in names - {"setup_s"}:
        assert callable(harness.metric_reader(n))
    for c in bm["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert set(conf["limits"]) == {"mean_gap"}
        arch = harness.load_module(conf["arch"])
        for attr in ("dims", "model_config", "params", "work", "TINY"):
            assert callable(getattr(arch, attr)) or attr == "TINY"
        assert arch.dims(conf).layers == conf["num_hidden_layers"]
        assert callable(harness.load_module(conf["reference"]).forward_logits)
    for w in bm["workloads"]:
        t = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json")
                       .read_text())
        assert generator.max_len(t) >= t["prompt_len"][3] + t["output_len"][3]
        per_layer = harness.metrics_of(bm, w["name"], True)
        moved = {m["moves"] for m in per_layer}
        assert moved <= {m["name"] for m in harness.metrics_of(
            bm, w["name"], False)}


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mixtral-chat",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_chip():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _step_call(kind, width=4, block_size=8, experts=8):
    """Arguments and outputs shaped as the program's warmed step takes and
    gives them (the unread ones as placeholders)."""
    lay = harness.DEFAULT_LAYOUT[kind]
    args = [None] * lay["args"]
    for i, dtype, ndim in lay["read"].values():
        args[i] = np.full((width, block_size)[:ndim], 1, dtype)
    out = [None] * (lay["stats"] + 1)
    out[lay["stats"]] = {"counts": np.ones((2, experts), np.float32)}
    return args, tuple(out)


@pytest.mark.parametrize("kind", ["dec", "chunk"])
@pytest.mark.parametrize("fault", [None, "one argument more", "mask in int32",
                                   "positions of another width", "no counts"])
def test_recorder_reads_only_the_step_it_knows(kind, fault):
    """The traced run's work counts read the step's positions, masks and
    gating counts by place; a step whose signature moved fails the run."""
    args, out = _step_call(kind)
    lay = harness.DEFAULT_LAYOUT[kind]
    pos = lay["read"]["pos" if kind == "dec" else "offsets"]
    mask = lay["read"]["mask"]
    if fault == "one argument more":
        args.append(None)
    elif fault == "mask in int32":
        args[mask[0]] = args[mask[0]].astype(np.int32)
    elif fault == "positions of another width":
        args[pos[0]] = args[pos[0]][:2]
    elif fault == "no counts":
        out = out[:lay["stats"]] + (None,)
    log = {"on": True, "decode": [], "chunk": [], "experts": 8}
    rec = harness._Recorder(lambda *a: out, (kind, 8, 81, 4, False), log)
    if fault is None:
        assert rec(*args) is out
        (got,) = log["decode" if kind == "dec" else "chunk"]
        assert got[next(iter(lay["read"]))] is args[pos[0]]
        assert got["mask"] is args[mask[0]]
    else:
        with pytest.raises(RuntimeError, match="signature|statistics"):
            rec(*args)
