"""Continuous-batching runtime: token-identity vs sequential generate(),
shared decode batches, staggered arrivals, and control-plane integration."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.migration import CostModel
from repro.core.policies import ClusterView, PlacementController, get_policy
from repro.data.pipeline import TaskTokenSource
from repro.launch.mesh import make_test_mesh
from repro.models import moe as M
from repro.models import transformer as tr
from repro.serving.api import Request
from repro.serving.engine import ServingEngine
from repro.serving.runtime import ServingRuntime


@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_config("mixtral-8x7b").reduced()
    mesh = make_test_mesh(1, 1)
    spec = M.EPSpec.build(mesh, cfg, ep_axes=("model",),
                          slots=cfg.num_experts, capacity=4096,
                          slot_capacity=8192)
    _, n_groups = cfg.layer_pattern()
    rt = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="ep", ep_spec=spec)
    rt_dense = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="dense")
    params_dense = tr.init_params(rt_dense, jax.random.PRNGKey(0))
    pl = M.uniform_placement(spec.n_ep, spec.slots, cfg.num_experts)
    pls = tr.stack_placement(pl, n_groups)
    params = dict(params_dense)
    params["groups"] = M.regather_ep_groups(params_dense["groups"], pls,
                                            n_groups)
    eng = ServingEngine(rt=rt, params=params, placement=pls,
                        dense_master=params_dense["groups"], max_len=64)
    src = TaskTokenSource("arith", cfg.vocab_size, seed=0)
    return cfg, spec, n_groups, eng, src


def _reference(eng, prompt, steps):
    gen, _ = eng.generate(prompt[None], steps=steps)
    return gen[0]


def test_concurrent_requests_share_batch_and_match_sequential(engine_setup):
    cfg, spec, n_groups, eng, src = engine_setup
    p1 = src.sample(1, 16)[0]
    p2 = src.sample(1, 12)[0]
    p3 = src.sample(1, 16)[0]
    refs = [_reference(eng, p, s) for p, s in
            [(p1, 6), (p2, 4), (p3, 5)]]

    rtm = ServingRuntime(eng, max_slots=4)
    rids = [rtm.enqueue(Request(prompt=p, max_new_tokens=s)).rid
            for p, s in [(p1, 6), (p2, 4), (p3, 5)]]
    out = rtm.run()
    # >= 2 concurrently arriving requests advanced in one decode batch
    assert rtm.max_concurrency >= 2
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(out[rid], ref)


def test_staggered_arrivals_match_sequential(engine_setup):
    """A request admitted mid-stream (rows at different cache positions)
    still decodes token-identically."""
    cfg, spec, n_groups, eng, src = engine_setup
    p1 = src.sample(1, 16)[0]
    p2 = src.sample(1, 12)[0]
    ref1 = _reference(eng, p1, 8)
    ref2 = _reference(eng, p2, 4)

    rtm = ServingRuntime(eng, max_slots=4)
    a = rtm.enqueue(Request(prompt=p1, max_new_tokens=8)).rid
    rtm.step()
    rtm.step()                       # p1 is several tokens ahead...
    b = rtm.enqueue(Request(prompt=p2,
                            max_new_tokens=4)).rid   # ...p2 joins mid-batch
    out = rtm.run()
    assert rtm.max_concurrency >= 2
    np.testing.assert_array_equal(out[a], ref1)
    np.testing.assert_array_equal(out[b], ref2)


def test_more_requests_than_slots(engine_setup):
    """Queueing: requests beyond the pool size wait and are admitted as
    slots free up; every output still matches sequential serving."""
    cfg, spec, n_groups, eng, src = engine_setup
    prompts = [src.sample(1, 12)[0] for _ in range(5)]
    refs = [_reference(eng, p, 3) for p in prompts]
    rtm = ServingRuntime(eng, max_slots=2)
    rids = [rtm.enqueue(Request(prompt=p, max_new_tokens=3)).rid
            for p in prompts]
    out = rtm.run()
    assert len(out) == 5
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(out[rid], ref)


def test_prefill_only_request(engine_setup):
    cfg, spec, n_groups, eng, src = engine_setup
    p = src.sample(1, 16)[0]
    ref = _reference(eng, p, 1)
    rtm = ServingRuntime(eng, max_slots=2)
    rid = rtm.enqueue(Request(prompt=p, max_new_tokens=1)).rid
    out = rtm.run()
    np.testing.assert_array_equal(out[rid], ref)


def test_runtime_applies_adopted_plans_and_preserves_function(engine_setup):
    cfg, spec, n_groups, eng, src = engine_setup
    cm = CostModel(expert_bytes=3 * cfg.d_model * cfg.d_ff * 2,
                   activation_bytes=cfg.d_model * 2, bandwidth=62.5e6,
                   tokens_per_horizon=1e6)
    ctrl = PlacementController(policy=get_policy("dancemoe"), cost=cm,
                               cluster=ClusterView.from_ep_spec(spec,
                                                                n_groups),
                               interval=2)
    rtm = ServingRuntime(eng, max_slots=2, controller=ctrl)
    assert ctrl.stats is eng.stats   # controller owns the engine's stats
    p = src.sample(1, 16)[0]
    before = _reference(eng, p, 6)
    rid = rtm.enqueue(Request(prompt=p, max_new_tokens=6)).rid
    out = rtm.run()
    np.testing.assert_array_equal(out[rid], before)
    assert ctrl.plan is not None     # at least the initial review ran
    after = _reference(eng, p, 6)
    np.testing.assert_array_equal(after, before)   # migration preserved fn


def test_submit_rejects_overlong_request(engine_setup):
    """Admission validation (satellite fix): the dense pool keeps the
    legacy per-row ``max_len`` bound; the paged pool validates against the
    *total pool capacity* instead, so a request longer than ``max_len`` is
    admissible whenever its pages fit."""
    cfg, spec, n_groups, eng, src = engine_setup
    rtm = ServingRuntime(eng, max_slots=2, paged=False)
    with pytest.raises(ValueError):
        rtm.enqueue(Request(prompt=src.sample(1, 60)[0],
                    max_new_tokens=10))       # 70 > max_len=64
    with pytest.raises(ValueError):
        Request(prompt=src.sample(1, 8)[0], max_new_tokens=0)
    # paged: 2 slots x 64 positions -> 8 blocks of 16 = 128 total
    rtm = ServingRuntime(eng, max_slots=2, block_size=16)
    assert rtm.paged
    rtm.enqueue(Request(prompt=src.sample(1, 60)[0],
                max_new_tokens=10))           # 70 <= 128: admissible
    with pytest.raises(ValueError):
        rtm.enqueue(Request(prompt=src.sample(1, 120)[0],
                    max_new_tokens=10))       # 130 > 128: rejected
    with pytest.raises(ValueError):
        Request(prompt=src.sample(1, 8)[0], max_new_tokens=0)


def test_vacant_slots_excluded_from_stats(engine_setup):
    """A 1-request stream in a 4-slot pool must ingest only the real
    request's activations — the 3 vacant rows' garbage routing is masked
    out of the gating statistics."""
    cfg, spec, n_groups, eng, src = engine_setup
    K = cfg.top_k
    eng.stats.reset()
    rtm = ServingRuntime(eng, max_slots=4)
    rtm.enqueue(Request(prompt=src.sample(1, 8)[0], max_new_tokens=4))
    rtm.run()
    # prefill: 8 tokens x K; 3 decode rounds x 1 active row x K — per group
    expected = (8 * K + 3 * K) * n_groups
    assert eng.stats.counts.sum() == pytest.approx(expected, rel=0.01)
    eng.stats.reset()


def test_first_review_waits_a_full_interval(engine_setup):
    """The controller's initial adoption must respect the review interval
    (not fire on decode round 1 with near-empty stats)."""
    cfg, spec, n_groups, eng, src = engine_setup
    ctrl = PlacementController(policy=get_policy("dancemoe"), cost=None,
                               cluster=ClusterView.from_ep_spec(spec,
                                                                n_groups),
                               interval=1000)
    rtm = ServingRuntime(eng, max_slots=2, controller=ctrl)
    rtm.enqueue(Request(prompt=src.sample(1, 8)[0], max_new_tokens=4))
    rtm.run()
    assert ctrl.plan is None and rtm.migrations == []   # interval not hit


def test_ingest_weight_scales_stats(engine_setup):
    """Satellite fix: ``_ingest`` must honor its weight argument."""
    cfg, spec, n_groups, eng, src = engine_setup
    mstats = {"counts_per_rank": np.ones((n_groups, spec.n_ep,
                                          cfg.num_experts))}
    eng.stats.reset()
    eng._ingest(mstats, weight=1.0)
    one = eng.stats.counts.copy()
    assert one.sum() > 0
    eng.stats.reset()
    eng._ingest(mstats, weight=2.5)
    np.testing.assert_allclose(eng.stats.counts, 2.5 * one)
    eng.stats.reset()


def test_prefill_stats_weighted_by_tokens(engine_setup):
    """A T-token prefill must contribute exactly T x the activation mass of
    one decode step (raw counts, no double weighting)."""
    cfg, spec, n_groups, eng, src = engine_setup
    T = 16
    eng.stats.reset()
    eng.generate(src.sample(1, T), steps=1)    # prefill + 1 decode
    mass1 = eng.stats.counts.sum()
    eng.stats.reset()
    eng.generate(src.sample(1, T), steps=3)    # prefill + 3 decodes
    mass3 = eng.stats.counts.sum()
    eng.stats.reset()
    decode_step_mass = (mass3 - mass1) / 2
    prefill_mass = mass1 - decode_step_mass
    assert decode_step_mass > 0
    assert prefill_mass / decode_step_mass == pytest.approx(T, rel=0.05)


# ---------------------------------------------------------------------------
# PR 7 satellites: bounded metrics reservoirs, pop_finished, failover
# eviction/re-admission, launch-round local_frac attribution, metering
# that never fails silently
# ---------------------------------------------------------------------------

from repro.serving.cluster import EdgeCluster  # noqa: E402
from repro.serving.net import ServerProfile, Topology  # noqa: E402
from repro.serving.runtime import Reservoir, _Pending  # noqa: E402


def test_reservoir_decimation_bounded_and_deterministic():
    r = Reservoir(cap=64)
    for k in range(10_000):
        r.append(float(k))
    assert r.count == 10_000            # true observation count survives
    assert 2 <= len(r) <= 64            # kept samples stay bounded
    kept = list(r)
    # systematic decimation: survivors are exactly the consecutive
    # multiples of the final stride (evenly spaced over the full stream)
    assert kept == [float(k * r.stride) for k in range(len(kept))]
    # percentiles stay representative of the full history
    assert np.percentile(kept, 50) == pytest.approx(
        float(np.percentile(np.arange(10_000), 50)), rel=0.05)
    # no RNG: an identical stream decimates identically (fault-schedule
    # reruns must stay bit-identical)
    r2 = Reservoir(cap=64)
    for k in range(10_000):
        r2.append(float(k))
    assert list(r2) == kept and r2.stride == r.stride
    with pytest.raises(ValueError, match="cap"):
        Reservoir(cap=1)


def test_perf_metrics_bounded_by_reservoir(engine_setup):
    """decode_round_s / ttft_s previously grew one entry per round/request
    forever; they are reservoirs now, and the perf section reports the
    true round count, not the kept-sample count."""
    cfg, spec, n_groups, eng, src = engine_setup
    rtm = ServingRuntime(eng, max_slots=2)
    assert rtm.decode_round_s.cap >= 2 and rtm.ttft_s.cap >= 2
    rtm.enqueue(Request(prompt=src.sample(1, 8)[0], max_new_tokens=4))
    rtm.run()
    pm = rtm.perf_metrics()
    assert pm["rounds_timed"] == rtm.decode_round_s.count > 0
    assert pm["decode_round_ms"]["p50"] > 0


def test_pop_finished_releases_bookkeeping(engine_setup):
    cfg, spec, n_groups, eng, src = engine_setup
    rtm = ServingRuntime(eng, max_slots=4)
    h1 = rtm.enqueue(Request(prompt=src.sample(1, 8)[0], max_new_tokens=4))
    h2 = rtm.enqueue(Request(prompt=src.sample(1, 12)[0], max_new_tokens=3))
    out = rtm.run()
    hit_rate = rtm.prefix_hit_rate
    popped = rtm.pop_finished()
    assert set(popped) == {h1.rid, h2.rid}
    np.testing.assert_array_equal(popped[h1.rid], out[h1.rid])
    # the per-request bookkeeping is released...
    assert not rtm.finished and not rtm.finished_at and not rtm.handles
    # ...but the rate denominators survive the pop
    assert rtm.prefix_hit_rate == hit_rate
    # a later pop returns only the newer results
    h3 = rtm.enqueue(Request(prompt=src.sample(1, 8)[0], max_new_tokens=2))
    rtm.run()
    assert set(rtm.pop_finished()) == {h3.rid}
    assert rtm.pop_finished() == {}


def test_evict_and_readmit_under_same_handle(engine_setup):
    """The cluster failover path: evict an in-flight request (pages
    recycled, invariants hold), then re-admit it under its original
    handle — the regenerated stream matches sequential generate()."""
    cfg, spec, n_groups, eng, src = engine_setup
    p1 = src.sample(1, 16)[0]
    ref1 = _reference(eng, p1, 6)
    rtm = ServingRuntime(eng, max_slots=2, prefix_cache=False)
    h1 = rtm.enqueue(Request(prompt=p1, max_new_tokens=6))
    h2 = rtm.enqueue(Request(prompt=src.sample(1, 12)[0], max_new_tokens=6))
    h3 = rtm.enqueue(Request(prompt=src.sample(1, 8)[0], max_new_tokens=2))
    for _ in range(3):                 # h1/h2 in flight, h3 still queued
        rtm.step()
    assert not h1.done
    emitted = rtm.evict(h1.rid)
    assert emitted == len(h1._tokens)  # tokens the victim must regenerate
    assert h1.rid not in rtm.handles
    assert rtm.evict(h3.rid) == 0      # queued victim: nothing emitted yet
    assert rtm.evict(999_999) == 0     # unknown rid: no-op
    rtm.check_invariants()
    old_rid = h1.rid
    h1._tokens.clear()                 # the stream restarts from scratch
    rtm.enqueue(Request(prompt=p1, max_new_tokens=6), handle=h1)
    assert h1.rid != old_rid           # re-bound to a fresh internal rid
    out = rtm.run()
    assert h1.done
    np.testing.assert_array_equal(out[h1.rid], ref1)
    np.testing.assert_array_equal(h1.result(), ref1)
    rtm.check_invariants()
    if rtm.paged:
        assert rtm.allocator.n_free == rtm.allocator.capacity_blocks


def test_drain_attributes_launch_round_local_frac(engine_setup):
    """Regression (pre-PR bug): ``_drain_tokens`` read the engine's
    mutable ``last_local_frac`` at *drain* time, so a round whose gating
    stats carried no local_frac — or any engine sharer ingesting between
    launch and drain — mis-credited a stale value to the draining slots.
    The round's own stats, captured at launch, are authoritative."""
    cfg, spec, n_groups, eng, src = engine_setup
    rtm = ServingRuntime(eng, max_slots=2, prefix_cache=False)
    h = rtm.enqueue(Request(prompt=src.sample(1, 8)[0], max_new_tokens=6))
    rtm.step()                          # prefill (+ first decode rounds)
    rtm.step()
    i, slot = next((i, s) for i, s in enumerate(rtm.slots)
                   if s is not None and s.rid == h.rid)
    assert not slot.prefilling and len(slot.tokens) < slot.need
    zero_counts = np.zeros((n_groups, spec.n_ep, cfg.num_experts))
    eng.last_local_frac = 0.25          # a sharer's stale value
    # a legal round record whose stats carry no local_frac: nothing may
    # be attributed (pre-PR code credited the stale 0.25 here)
    before = (slot.lf_sum, slot.lf_rounds)
    rtm._drain_one(_Pending(
        kind="decode", tick=rtm.ticks, rows=[(0, i, h.rid)],
        nxt=np.array([3], np.int32),
        mstats={"counts_per_rank": zero_counts}))
    assert (slot.lf_sum, slot.lf_rounds) == before
    assert slot.tokens[-1] == 3         # the token itself still lands
    # a round that does carry local_frac attributes its own value
    eng.last_local_frac = 0.25
    rtm._drain_one(_Pending(
        kind="decode", tick=rtm.ticks, rows=[(0, i, h.rid)],
        nxt=np.array([4], np.int32),
        mstats={"counts_per_rank": zero_counts,
                "local_frac": np.array([0.5])}))
    assert slot.lf_rounds == before[1] + 1
    assert slot.lf_sum == pytest.approx(before[0] + 0.5)
    eng.stats.reset()


def _solo_topology() -> Topology:
    return Topology((ServerProfile("solo", mem_bytes=8e9),),
                    np.array([[500e6 / 8]]), np.array([[0.0]]))


def test_meter_mismatch_raises_when_it_never_succeeded(engine_setup):
    """Regression (pre-PR bug): a persistently mismatched residency view
    made ``step()`` skip metering silently forever — ``metrics()['net']``
    read zero dispatch bytes with no hint anything was wrong."""
    cfg, spec, n_groups, eng, src = engine_setup
    ec = EdgeCluster("runtime", engine=eng, n_servers=1,
                     topology=_solo_topology(),
                     runtime_opts=dict(max_slots=2))
    ec.backend._residency = lambda: np.zeros((1, 1, 1))   # wrong shape
    with pytest.raises(RuntimeError, match="metering"):
        for _ in range(40):
            ec.step()
    assert ec.backend.meter_skips >= 32
    assert ec.metrics()["net"]["meter_skips"] >= 32


def test_meter_transient_mismatch_is_tolerated(engine_setup):
    """A mismatch window after metering has worked (e.g. plan granularity
    churn mid-migration) is counted and surfaced, never fatal."""
    cfg, spec, n_groups, eng, src = engine_setup
    ec = EdgeCluster("runtime", engine=eng, n_servers=1,
                     topology=_solo_topology(),
                     runtime_opts=dict(max_slots=2))
    ec.submit(Request(prompt=src.sample(1, 8)[0], max_new_tokens=2))
    ec.run()
    assert ec.backend._meter_ok > 0 and ec.backend.meter_skips == 0
    ec.backend._residency = lambda: np.zeros((1, 1, 1))
    for _ in range(40):                 # far past the streak threshold
        ec.step()
    assert ec.backend.meter_skips == 40
    assert ec.metrics()["net"]["meter_skips"] == 40


def test_local_frac_warm_vs_sync_subprocess():
    """Satellite regression: per-request local_frac attribution must be
    identical between the sync and warm (zero-stall) loops when nothing
    queues. Runs on 2 fake EP ranks in a subprocess (locality on a single
    rank is trivially 1.0; the fake device count must not leak into this
    process — the tier-1 convention, see test_multidevice)."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = str(Path(__file__).parent.parent / "src")
    r = subprocess.run(
        [sys.executable, str(Path(__file__).parent / "md_scripts"
                             / "local_frac_warm_sync.py")],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, \
        f"local_frac_warm_sync.py failed:\n{r.stdout}\n{r.stderr}"
    assert "ALL OK" in r.stdout


@pytest.mark.parametrize("dense_pool", [False, True])
def test_build_server_is_dropless_and_token_identical(dense_pool):
    """The serving CLI's construction path (``chip_smoke.py`` drives the
    same one): EP capacities sized so no token can drop (the dense pool
    prefills up to ``slots`` prompts in one step), the DanceMoE controller
    applying a plan mid-stream, the warmed zero-stall loop on the paged
    pool — token-identical to sequential per-request generate()."""
    from repro.launch.serve import build_server, ep_capacities

    cfg = get_config("mixtral-8x7b").reduced()
    engine, _, rtm = build_server(cfg, max_len=40, slots=2, block_size=8,
                                  dense_pool=dense_pool,
                                  warmup=not dense_pool, review_rounds=4)
    spec = engine.rt.ep_spec
    rows = 2 * 40 if dense_pool else 40
    assert (spec.capacity, spec.slot_capacity) == \
        ep_capacities(rows, cfg.top_k, 1) == (rows * cfg.top_k, rows)
    src = TaskTokenSource("build-server", cfg.vocab_size, seed=1)
    prompts = [src.sample(1, 16)[0] for _ in range(3)]
    handles = [rtm.enqueue(Request(prompt=p, max_new_tokens=8))
               for p in prompts]
    rtm.run()
    assert rtm.migrations
    assert rtm.traces_after_warmup == 0 or dense_pool
    for p, h in zip(prompts, handles):
        ref, _ = engine.generate(p[None], steps=8)
        np.testing.assert_array_equal(h.result(), ref[0])
