"""The serving tick's wall-clock instrumentation: ``serve.*`` host spans on
the profiler's host plane, named scopes in the compiled steps, the
handles' ``wall`` stamps, and the runtime's prefill-token and EP row
counters."""

import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.data.pipeline import TaskTokenSource
from repro.launch.mesh import make_test_mesh
from repro.launch.serve import build_controller
from repro.models import moe as M
from repro.models import transformer as tr
from repro.serving.api import Request
from repro.serving.cluster import EdgeCluster
from repro.serving.engine import ServingEngine
from repro.serving.obs import SpanKind
from repro.serving.runtime import ServingRuntime

CFG = get_config("mixtral-8x7b").reduced()
SCOPES = ("attn", "moe.route", "moe.dispatch", "moe.expert_ffn",
          "moe.combine", "head")


def _engine(slot_capacity: int = 8192) -> ServingEngine:
    mesh = make_test_mesh(1, 1)
    spec = M.EPSpec.build(mesh, CFG, ep_axes=("model",),
                          slots=CFG.num_experts, capacity=4096,
                          slot_capacity=slot_capacity)
    _, n_groups = CFG.layer_pattern()
    rt = tr.Runtime(cfg=CFG, mesh=mesh, moe_impl="ep", ep_spec=spec)
    dense = tr.init_params(tr.Runtime(cfg=CFG, mesh=mesh, moe_impl="dense"),
                           jax.random.PRNGKey(0))
    pls = tr.stack_placement(
        M.uniform_placement(spec.n_ep, spec.slots, CFG.num_experts), n_groups)
    params = {**dense, "groups": M.regather_ep_groups(dense["groups"], pls,
                                                      n_groups)}
    return ServingEngine(rt=rt, params=params, placement=pls,
                         dense_master=dense["groups"], max_len=48)


@pytest.fixture(scope="module")
def engine():
    return _engine()


@pytest.fixture(scope="module")
def src():
    return TaskTokenSource("spans", CFG.vocab_size, seed=3)


def _requests(src, n: int = 4):
    return [Request(prompt=src.sample(1, 5 + 6 * k)[0],
                    max_new_tokens=3 + k) for k in range(n)]


def _warm(engine, **kw) -> ServingRuntime:
    return ServingRuntime(engine, max_slots=2, block_size=8, warmup=True,
                          warmup_origins="untagged", **kw)


def _inside(child, parent) -> bool:
    return (parent.start_ns <= child.start_ns
            and child.start_ns + child.duration_ns
            <= parent.start_ns + parent.duration_ns)


def test_tick_spans_nest_on_the_profiler_host_plane(engine, src, tmp_path):
    rtm = _warm(engine, controller=build_controller(engine, "dancemoe", 2))
    for r in _requests(src):
        rtm.enqueue(r)
    with jax.profiler.trace(str(tmp_path)):
        rtm.run()
    path = next(tmp_path.rglob("*.xplane.pb"))
    # names and times only: reading event stats warns on this JAX
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.setdefault(ev.name, []).append(ev)
    ticks = spans["serve.tick"]
    assert len(ticks) == rtm.ticks
    # one chunk round a tick: every phase once a tick, work or not
    for name in ("admit", "prefill", "decode", "drain"):
        kids = [k for k in spans[f"serve.{name}"]
                if any(_inside(k, t) for t in ticks)]
        assert len(kids) == rtm.ticks
    # and run() ends with a flush: one drain after the last tick
    assert len(spans["serve.drain"]) == rtm.ticks + 1
    assert len(spans["serve.admit"]) == len(spans["serve.decode"])
    drains = spans["serve.drain"]
    for name in ("wait", "review"):
        assert all(any(_inside(k, d) for d in drains)
                   for k in spans.get(f"serve.{name}", []))
    assert spans["serve.review"] and spans["serve.migrate"]
    assert all(any(_inside(m, r) for r in spans["serve.review"])
               for m in spans["serve.migrate"])
    assert set(spans) <= {f"serve.{k}" for k in SpanKind.HOST}


def test_step_executables_carry_every_scope(engine):
    rtm = _warm(engine)
    for kind in ("dec", "chunk"):
        text = engine.paged_executable(kind, 8, rtm.max_pages, 2,
                                       False).as_text()
        assert text.startswith(f"HloModule jit__{kind}")
        names = re.findall(r'op_name="([^"]*)"', text)
        for scope in SCOPES:
            assert any(f"/{scope}/" in n for n in names), (kind, scope)


def test_wall_stamps_are_ordered_and_feed_ttft(engine, src):
    for warmup in (False, True):
        rtm = (_warm(engine) if warmup
               else ServingRuntime(engine, max_slots=2, block_size=8))
        handles = [rtm.enqueue(r) for r in _requests(src)]
        rtm.run()
        for h in handles:
            w = h.wall
            assert (w["enqueued"] <= w["admitted"] <= w["first_token"]
                    <= w["finished"])
        ttft = sorted(h.wall["first_token"] - h.wall["enqueued"]
                      for h in handles)
        assert sorted(rtm.ttft_s) == pytest.approx(ttft)
        assert rtm.perf_metrics()["ttft_ms"]["p99"] > 0


def test_prefill_tokens_count_every_prompt_token(engine, src):
    rtm = _warm(engine, prefix_cache=False)
    reqs = _requests(src, 5)
    for r in reqs:
        rtm.enqueue(r)
    rtm.run()
    assert rtm.prefill_tokens == sum(len(r.prompt) for r in reqs)
    assert rtm.prefill_calls <= rtm.chunks_executed
    assert rtm.ep_rows_dropped == 0
    assert rtm.perf_metrics()["ep_rows_dropped"] == 0


@pytest.mark.parametrize("mode", ["decode", "chunk"])
def test_ep_layer_counts_its_capacity_drops(mode):
    """With one EP rank every expert has one slot of ``C2`` rows, so the
    drops are exactly each expert's (row, expert) pairs beyond ``C2``: a
    capacity below what the rows can fill is used as set, not clamped."""
    mesh = make_test_mesh(1, 1)
    spec = M.EPSpec.build(mesh, CFG, ep_axes=("model",),
                          slots=CFG.num_experts, capacity=4096,
                          slot_capacity=2)
    p = M.moe_params_ep(jax.random.PRNGKey(1), CFG, spec)
    pl = M.uniform_placement(1, spec.slots, CFG.num_experts)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 6, CFG.d_model))
    _, st = M.moe_apply_ep(p, CFG, x, mesh=mesh, spec=spec, placement=pl,
                           mode=mode)
    n_e = np.asarray(st["counts"])
    assert int(st["dropped"]) == int(np.maximum(n_e - 2, 0).sum()) > 0
    assert int(st["rows_filled"]) == int(np.minimum(n_e, 2).sum())
    assert int(st["rows_computed"]) == spec.slots * 2


def test_runtime_sums_ep_drops_at_drain(src):
    rtm = ServingRuntime(_engine(slot_capacity=1), max_slots=2,
                         block_size=8)
    for r in _requests(src, 2):
        rtm.enqueue(r)
    rtm.run()
    assert rtm.ep_rows_dropped > 0
    assert rtm.perf_metrics()["ep_rows_dropped"] == rtm.ep_rows_dropped


def test_runtime_counts_the_ep_rows_it_computes(engine, src):
    """Every call computes S x C2 slot rows a layer, C2 clamped to its own
    rows: 8 for a decode batch of at most 2 (one sublane tile), a chunk's
    batch x block_size rows; the live pairs fill part of them."""
    rtm = _warm(engine, prefix_cache=False)
    for r in _requests(src, 3):
        rtm.enqueue(r)
    rtm.run()
    S, (_, layers) = engine.rt.ep_spec.slots, CFG.layer_pattern()
    assert rtm.ep_rows_computed == layers * S * (
        8 * rtm.rounds + rtm.block_size * rtm.prefill_rows)
    assert (CFG.top_k * layers * rtm.prefill_tokens < rtm.ep_rows_filled
            <= rtm.ep_rows_computed)
    perf = rtm.perf_metrics()
    assert perf["ep_rows_computed"] == rtm.ep_rows_computed
    assert perf["ep_rows_filled"] == rtm.ep_rows_filled


def test_cluster_sums_the_ep_row_counters(engine, src):
    ec = EdgeCluster("runtime", engine=engine, n_servers=2,
                     shared_runtime=False,
                     runtime_opts=dict(max_slots=2, block_size=8))
    for k, r in enumerate(_requests(src, 4)):
        ec.submit(Request(prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                          origin=k % 2))
    ec.run()
    perf = ec.metrics()["perf"]
    for key in ("ep_rows_dropped", "ep_rows_computed", "ep_rows_filled"):
        assert perf[key] == sum(getattr(r, key)
                                for r in ec.backend.runtimes)
    assert 0 < perf["ep_rows_filled"] <= perf["ep_rows_computed"]
