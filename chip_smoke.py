"""Smoke run of the MoE serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host with four chips

Mixtral-8x7B at its published widths in bf16, cut to 2 layers (depth is the
only cut), with random weights drawn from a fixed seed. One chip: the Pallas
expert-FFN kernel against its reference, then 8 requests served through the
same construction path as ``python -m repro.launch.serve --full --warmup``
(EP MoE layer, DanceMoE placement controller, paged KV pool with the prefix
cache, AOT-warmed zero-stall loop), checked against the engine's sequential
``generate()``. Four chips: the ``EdgeCluster`` runtime backend with one EP
rank per chip and a DanceMoE plan adoption that moves expert weights
between the chips, checked against the one-chip path on device 0.

Exits non-zero, without a result line, when JAX finds no TPU or any check
fails. The last line of a passing run is the JSON result object. Times
printed here are host wall clocks of a smoke run, not device metrics.
"""

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "mixtral-8x7b"
LAYERS = 2  # depth cut: 2 of 32 layers fit one 16 GB chip beside a master copy
SEED = 0
N_REQUESTS, PROMPT, SHARED_PREFIX, NEW_TOKENS = 8, 128, 64, 32
SLOTS = 4
REVIEW_ROUNDS = 8  # placement review period, in decode rounds
BLOCK_SIZE = 16
MAX_LEN = PROMPT + NEW_TOKENS + 8  # as the serving CLI sizes it
# KV blocks for every request at once plus the null block: the prefix cache
# then keeps every prompt's served first-step logits (see check_tokens)
BLOCKS = 1 + N_REQUESTS * -(-MAX_LEN // BLOCK_SIZE)
# Token-or-logit check against the engine's sequential path (prefill +
# decode, batch 1), bf16 both ways. Greedy streams need not be identical:
# the two paths round differently (other batch shapes, paged vs dense
# attention), and where a router scores two experts nearly alike bf16
# rounding can send one token to another expert, which moves that step's
# logits by up to ~20% of max|logit| (one step in 128 on a v5e at this
# width). So, relative to max|logit|:
# - first-step logits of every request agree within FIRST_LOGITS_TOL
#   (2**-5 = eight bf16 unit roundoffs; EP vs the dense MoE reference
#   differ by ~2**-7 here);
# - teacher-forced along the served tokens, at least AGREE_MIN of the
#   served tokens score within LOGIT_TOL of the reference's best logit at
#   their step. A broken path disagrees at most steps.
# All reference logits must be finite.
FIRST_LOGITS_TOL = 2.0**-5
LOGIT_TOL = 2.0**-5
AGREE_MIN = 0.9
GMM_TOL = 5e-2  # kernel vs reference in bf16, as tests/test_kernels.py


def smoke_config():
    from repro.configs import get_config

    return dataclasses.replace(get_config(ARCH), num_layers=LAYERS)


def make_prompts(vocab_size: int) -> list:
    """Half the prompts share a SHARED_PREFIX-token system prompt."""
    from repro.data.pipeline import TaskTokenSource

    src = TaskTokenSource("chip-smoke", vocab_size, seed=SEED)
    shared = src.sample(1, SHARED_PREFIX)[0]
    prompts = []
    for i in range(N_REQUESTS):
        if i % 2:
            prompts.append(src.sample(1, PROMPT)[0])
        else:
            tail = src.sample(1, PROMPT - SHARED_PREFIX)[0]
            prompts.append(np.concatenate([shared, tail]))
    return prompts


def kernel_phase(cfg) -> None:
    """The expert-FFN Pallas kernel, compiled for the chip (no interpret
    mode) and run once at the config's width against kernels/ref.py."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import moe_gmm, ref

    S, C, D, F = cfg.num_experts, 128, cfg.d_model, cfg.d_ff
    ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
    bf16 = jnp.bfloat16
    x = jax.random.normal(ks[0], (S, C, D), bf16) * 0.5
    w1 = jax.random.normal(ks[1], (S, D, F), bf16) * D**-0.5
    w3 = jax.random.normal(ks[2], (S, D, F), bf16) * D**-0.5
    w2 = jax.random.normal(ks[3], (S, F, D), bf16) * F**-0.5
    t0 = time.perf_counter()
    exe = moe_gmm.moe_gmm.lower(x, w1, w3, w2, interpret=False).compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in exe.as_text():
        raise RuntimeError("moe_gmm compiled without its Mosaic kernel")
    y = np.asarray(exe(x, w1, w3, w2), np.float32)
    yr = np.asarray(ref.moe_gmm_ref(x, w1, w3, w2), np.float32)
    err = float(np.max(np.abs(y - yr)))
    print(
        f"moe_gmm on chip [S={S} C={C} D={D} F={F}] bf16: compiled in "
        f"{compile_s:.3f} s, max |kernel - ref| = {err:.6g} (tol {GMM_TOL})"
    )
    np.testing.assert_allclose(y, yr, atol=GMM_TOL, rtol=GMM_TOL)


def teacher_forced_gaps(engine, prompt, served):
    """The engine's sequential path (prefill, then one decode step per
    token) fed the served tokens: its first-step logits, and per served
    token how far that token's logit sits below the step's best logit,
    relative to the step's max |logit|."""
    import jax.numpy as jnp

    logits, cache, _ = engine._prefill(
        engine.params, jnp.asarray(prompt[None]), engine.placement
    )
    first, gaps = np.asarray(logits[0], np.float32), []
    for i, tok in enumerate(served):
        if i:
            logits, cache, _ = engine._decode(
                engine.params,
                cache,
                jnp.asarray([[served[i - 1]]], jnp.int32),
                jnp.int32(len(prompt) + i - 1),
                engine.placement,
            )
        lg = np.asarray(logits[0], np.float32)
        if not np.isfinite(lg).all():
            raise RuntimeError(f"non-finite reference logits at step {i}")
        gaps.append((lg.max() - lg[tok]) / np.abs(lg).max())
    return first, np.asarray(gaps)


def check_tokens(engine, prompts, served, first_logits, label: str) -> None:
    """Served tokens and first-step logits against the engine's sequential
    ``generate()`` path (see FIRST_LOGITS_TOL). Raises on failure."""
    identical, first_err, gaps = 0, 0.0, []
    for p, s, lg in zip(prompts, served, first_logits):
        ref, _ = engine.generate(p[None], steps=len(s))
        identical += int(np.array_equal(ref[0], s))
        first, g = teacher_forced_gaps(engine, p, s)
        lg = np.asarray(lg, np.float32)
        if not np.isfinite(lg).all():
            raise RuntimeError(f"non-finite served logits ({label})")
        err = float(np.abs(lg - first).max() / np.abs(first).max())
        first_err = max(first_err, err)
        gaps.append(g)
    gaps = np.concatenate(gaps)
    agree = float((gaps <= LOGIT_TOL).mean())
    print(
        f"token check ({label}): {identical}/{len(served)} requests "
        f"token-identical to sequential generate(); first-step logits differ "
        f"by <= {first_err:.6g} x max|logit| (tol {FIRST_LOGITS_TOL}); "
        f"teacher-forced: {float((gaps == 0).mean()):.4f} of served tokens are "
        f"the reference argmax, {agree:.4f} within {LOGIT_TOL} (min {AGREE_MIN}), "
        f"worst gap {float(gaps.max()):.6g}"
    )
    if first_err > FIRST_LOGITS_TOL or agree < AGREE_MIN:
        raise RuntimeError(f"token check failed ({label})")


def first_step_logits(runtime, prompts) -> list:
    """Each prompt's served first-step logits, kept by the radix prefix
    cache for block-aligned prompts (the pool is sized so none is
    evicted)."""
    out = [runtime.prefix_cache.lookup(p).logits for p in prompts]
    if any(lg is None for lg in out):
        raise RuntimeError("served first-step logits were evicted")
    return out


def serve_one_chip(cfg, prompts):
    """The serving CLI's construction path on device 0; returns the engine
    and the served token streams."""
    import jax.numpy as jnp

    from repro.launch.serve import build_server
    from repro.serving.api import Request

    t0 = time.perf_counter()
    engine, controller, runtime = build_server(
        cfg,
        max_len=MAX_LEN,
        slots=SLOTS,
        block_size=BLOCK_SIZE,
        blocks=BLOCKS,
        warmup=True,
        review_rounds=REVIEW_ROUNDS,
        dtype=jnp.bfloat16,
    )
    spec = engine.rt.ep_spec
    print(
        f"EP layer: {spec.n_ep} rank x {spec.slots} slots, dropless "
        f"capacities C={spec.capacity} C2={spec.slot_capacity}"
    )
    print(
        f"build: {time.perf_counter() - t0:.3f} s, of which warmup compiled "
        f"{runtime.executables_compiled} executables in "
        f"{runtime.warmup_seconds:.3f} s"
    )
    handles = [
        runtime.enqueue(Request(prompt=p, max_new_tokens=NEW_TOKENS)) for p in prompts
    ]
    t0 = time.perf_counter()
    runtime.run()
    wall = time.perf_counter() - t0
    served = [h.result() for h in handles]
    n_tok = sum(len(s) for s in served)
    print(
        f"served {len(handles)} requests / {n_tok} tokens in {wall:.3f} s "
        f"host wall (smoke timing, not a device metric); prefix hits "
        f"{runtime.prefix_hits}, tokens skipped {runtime.prefix_tokens_skipped}"
    )
    if not all(h.done and len(s) == NEW_TOKENS for h, s in zip(handles, served)):
        raise RuntimeError("a request did not finish with its token count")
    reviews = [e for e in controller.events if "adopted" in e]
    print(
        f"placement: {len(reviews)} reviews, {len(runtime.migrations)} plans "
        f"applied; traces_after_warmup={runtime.traces_after_warmup}"
    )
    if not runtime.migrations:
        raise RuntimeError("no placement plan was applied")
    if runtime.traces_after_warmup != 0:
        raise RuntimeError("the serving loop traced after warmup")
    return engine, served, first_step_logits(runtime, prompts)


def bytes_in_use(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def one_chip(cfg) -> None:
    kernel_phase(cfg)
    gc.collect()
    prompts = make_prompts(cfg.vocab_size)
    engine, served, first = serve_one_chip(cfg, prompts)
    check_tokens(engine, prompts, served, first, "one chip")


def four_chips(cfg) -> None:
    """EdgeCluster runtime backend, one EP rank per chip, against the
    one-chip path on device 0 in this process."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import build_controller, build_engine, step_rows
    from repro.serving.api import Request
    from repro.serving.cluster import EdgeCluster

    devices = jax.devices()[:4]
    prompts = make_prompts(cfg.vocab_size)
    engine = build_engine(
        cfg,
        make_test_mesh(1, 4),
        max_len=MAX_LEN,
        rows=step_rows(max_len=MAX_LEN, slots=SLOTS, block_size=BLOCK_SIZE),
        dtype=jnp.bfloat16,
    )
    spec = engine.rt.ep_spec
    controller = build_controller(engine, "dancemoe", REVIEW_ROUNDS)
    before = bytes_in_use(devices)
    s2e_before = np.asarray(engine.placement.slot_to_expert)
    cluster = EdgeCluster(
        "runtime",
        engine=engine,
        n_servers=4,
        controller=controller,
        runtime_opts=dict(
            max_slots=SLOTS,
            block_size=BLOCK_SIZE,
            n_blocks=BLOCKS,
            warmup=True,
            warmup_origins="tagged",
        ),
    )
    handles = [
        cluster.submit(Request(prompt=p, max_new_tokens=NEW_TOKENS, origin=i % 4))
        for i, p in enumerate(prompts)
    ]
    cluster.run()
    served = [np.asarray(h.result()) for h in handles]
    first = first_step_logits(cluster.backend.runtimes[0], prompts)
    if not all(h.done and len(s) == NEW_TOKENS for h, s in zip(handles, served)):
        raise RuntimeError("a request did not finish with its token count")
    after = bytes_in_use(devices)
    moved = int((np.asarray(engine.placement.slot_to_expert) != s2e_before).sum())
    expert_bytes = 3 * cfg.d_model * cfg.d_ff * 2
    print(f"EP layer: {spec.n_ep} ranks x {spec.slots} slots, one rank per chip")
    print(f"bytes_in_use per device before serving: {before}")
    print(f"bytes_in_use per device after serving:  {after}")
    perf = cluster.backend.perf()
    print(
        f"warmup compiled {perf['executables_compiled']} executables in "
        f"{perf['warmup_seconds']:.3f} s; traces_after_warmup="
        f"{perf['traces_after_warmup']}"
    )
    print(
        f"placement: {len(cluster.migrations)} plans applied; {moved} of "
        f"{s2e_before.size} expert slots now hold another expert, re-gathered "
        f"from the master shards across the chips ({moved * expert_bytes} bytes)"
    )
    ep_share = spec.slots * s2e_before.shape[0] * expert_bytes
    if min(after) < ep_share or not moved or not cluster.migrations:
        raise RuntimeError("expert weights were not spread and moved over chips")
    if perf["traces_after_warmup"] != 0:
        raise RuntimeError("the serving loop traced after warmup")
    del cluster, controller, engine, handles
    gc.collect()
    print(f"bytes_in_use per device once freed: {bytes_in_use(devices)}")
    ref_engine, served_one, _ = serve_one_chip(cfg, prompts)
    same = sum(np.array_equal(a, b) for a, b in zip(served, served_one))
    print(f"four chips vs one chip: {same}/{len(served)} token streams identical")
    check_tokens(ref_engine, prompts, served, first, "four chips vs one-chip path")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the four-chip EdgeCluster phase and its comparison",
    )
    args = ap.parse_args()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d0.platform!r})")
    n_chips = 4 if args.four_chips else 1
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, JAX sees {len(devices)}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache = Path(use_compile_cache())
    n_cached = len(list(cache.iterdir())) if cache.is_dir() else 0
    print(f"device: {d0.platform} {d0.device_kind} x{len(devices)}")
    print(f"compile cache: {cache} ({n_cached} entries at start)")
    cfg = smoke_config()
    print(
        f"config: {cfg.name} at published widths (d_model {cfg.d_model}, d_ff "
        f"{cfg.d_ff}, {cfg.num_heads} heads over {cfg.num_kv_heads} KV heads of "
        f"{cfg.head_dim}, {cfg.num_experts} experts top-{cfg.top_k}, vocab "
        f"{cfg.vocab_size}), bf16, EP MoE path; reduced: num_layers 32 -> "
        f"{cfg.num_layers} (depth only); weights random from seed {SEED}"
    )
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(cfg)
    peak = [d.memory_stats()["peak_bytes_in_use"] for d in devices[:n_chips]]
    print(f"peak_bytes_in_use per device: {peak}")
    print(f"smoke wall: {time.perf_counter() - t0:.3f} s")
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
