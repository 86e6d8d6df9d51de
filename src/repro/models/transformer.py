"""Decoder stack assembly: scan-over-layer-groups transformer covering all
assigned families (dense / moe / ssm / hybrid / vlm / audio backbones).

Entry points (all pure functions of a ``Runtime``):
  init_params  — parameter pytree (group params stacked for lax.scan)
  loss_fn      — causal-LM loss + MoE aux losses + activation stats
  prefill      — full-sequence forward, returns last-token logits + cache
  decode_step  — one token against the cache (the serve_step of the dry-run)
  init_cache   — allocate the decode cache (full or sliding-window ring)
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN, MLP, MOE, MAMBA1, MAMBA2, SHARED_ATTN,
                                ModelConfig)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.layers import embed_init, mlp_params, mlp_apply, rms_norm, \
    softmax_xent


@dataclasses.dataclass(frozen=True)
class Runtime:
    """Model + distribution context (static: part of the jit closure)."""
    cfg: ModelConfig
    mesh: Any = None                  # jax Mesh (None = single device tests)
    moe_impl: str = "dense"           # 'dense' | 'ep'
    ep_spec: Any = None               # EPSpec when moe_impl == 'ep'
    dtype: Any = jnp.float32
    use_kernel: bool = False
    window: int = 0                   # >0: sliding-window attention active
    loss_chunk: int = 2048
    cache_seq_sharded: bool = False   # long-context: shard KV cache over seq
    scan_layers: bool = True          # False: unroll (exact cost_analysis)
    layout: str = "tp"                # tp | sp (seq-parallel residual) |
                                      # cp (replicated weights, ctx-parallel)
    remat_policy: str = "none"        # none | dots (save matmul/psum outputs)
    kv_quant: bool = False            # int8 KV cache (beyond-paper)
    kv_quant_consistent: bool = False  # prefill attends to dequantized k/v
                                      # (serve-consistent: full and paged
                                      # chunked prefill agree bit-wise)

    @property
    def ep(self) -> int:
        """Model-axis width (for head padding)."""
        return self.mesh.shape["model"] if self.mesh is not None else 1

    @property
    def ep_axes(self) -> tuple[str, ...]:
        return self.ep_spec.axes if self.ep_spec is not None else ("model",)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _block_params(rt: Runtime, kind: str, key):
    cfg, dt = rt.cfg, rt.dtype
    if kind in (ATTN, SHARED_ATTN):
        return attn.attn_params(key, cfg, rt.ep, dt)
    if kind == MLP:
        p = mlp_params(key, cfg.d_model, cfg.d_ff, dt)
        p["norm"] = jnp.ones((cfg.d_model,), dt)
        return p
    if kind == MOE:
        if rt.moe_impl == "ep":
            return moe_mod.moe_params_ep(key, cfg, rt.ep_spec, dt)
        return moe_mod.moe_params_dense(key, cfg, dt)
    if kind == MAMBA1:
        return ssm.mamba1_params(key, cfg, dt)
    if kind == MAMBA2:
        return ssm.mamba2_params(key, cfg, dt)
    raise ValueError(kind)


def init_params(rt: Runtime, key) -> dict:
    cfg = rt.cfg
    pattern, n_groups = cfg.layer_pattern()
    k_embed, k_head, k_shared, k_groups = jax.random.split(key, 4)
    params: dict = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model), rt.dtype),
        "final_norm": jnp.ones((cfg.d_model,), rt.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(
            k_head, (cfg.d_model, cfg.vocab_size), rt.dtype)
    if SHARED_ATTN in pattern:
        params["shared_attn"] = _block_params(rt, SHARED_ATTN, k_shared)
    groups: dict = {}
    for i, kind in enumerate(pattern):
        if kind == SHARED_ATTN:
            continue
        keys = jax.random.split(jax.random.fold_in(k_groups, i), n_groups)
        per = [_block_params(rt, kind, keys[g]) for g in range(n_groups)]
        groups[f"b{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    params["groups"] = groups
    return params


# ---------------------------------------------------------------------------
# Group application
# ---------------------------------------------------------------------------

def _sp_active(rt: Runtime, mode: str) -> bool:
    """Sequence-parallel residual stream (layout 'sp'): activations between
    blocks stay sharded over the model axis on the sequence dim; each
    TP sublayer all-gathers its input once and reduce-scatters its output —
    half the bytes of the baseline per-sublayer all-reduce, and the EP MoE
    dispatch layout becomes a free reshape. Serving steps (decode, paged
    chunk prefill) keep the replicated residual path."""
    return rt.layout == "sp" and rt.mesh is not None \
        and mode not in ("decode", "chunk")


def _sp_gather(rt: Runtime, x):
    from repro.models import sharding as sh
    b = tuple(a for a in rt.mesh.axis_names if a != "model")
    return sh.constrain(rt.mesh, x, P(b, None, None))


def _sp_scatter(rt: Runtime, x):
    from repro.models import sharding as sh
    b = tuple(a for a in rt.mesh.axis_names if a != "model")
    return sh.constrain(rt.mesh, x, P(b, "model", None))


def _apply_block(rt: Runtime, kind: str, p, h, *, mode, cache, pos,
                 placement, token_mask=None, paged=None, origin=None):
    cfg = rt.cfg
    window = rt.window
    sp = _sp_active(rt, mode)
    if kind in (ATTN, SHARED_ATTN):
        h_in = _sp_gather(rt, h) if sp else h
        with jax.named_scope("attn"):
            out, c = attn.attn_apply(
                p, cfg, h_in, ep=rt.ep, mode=mode, cache=cache, pos=pos,
                window=window, norm_eps=cfg.norm_eps,
                use_kernel=rt.use_kernel and mode not in ("decode", "chunk"),
                mesh=rt.mesh,
                cache_seq_sharded=rt.cache_seq_sharded, residual=not sp,
                gather_kv=rt.layout in ("cp", "fsdp"), paged=paged,
                quant_consistent=rt.kv_quant_consistent)
        if sp:
            out = h + _sp_scatter(rt, out)          # reduce-scatter the delta
        return out, c
    if kind == MLP:
        x = rms_norm(h, p["norm"], cfg.norm_eps)
        if sp:
            x = _sp_gather(rt, x)
        delta = mlp_apply(p, x)
        if sp:
            delta = _sp_scatter(rt, delta)
        return h + delta, None
    if kind == MOE:
        if rt.moe_impl == "ep":
            out, stats = moe_mod.moe_apply_ep(
                p, cfg, h, mesh=rt.mesh, spec=rt.ep_spec,
                placement=placement, mode=mode, use_kernel=rt.use_kernel,
                norm_eps=cfg.norm_eps,
                # serving steps (decode/chunk) use the masked dispatch
                # branch — the seq-sharded fast path ignores token_mask
                seq_sharded_out=(rt.layout in ("sp", "cp", "fsdp")
                                 and mode not in ("decode", "chunk")),
                token_mask=token_mask, origin=origin)
        else:
            out, stats = moe_mod.moe_apply_dense(p, cfg, h,
                                                 norm_eps=cfg.norm_eps)
        return out, stats
    if kind == MAMBA1:
        return ssm.mamba1_apply(p, cfg, h, mode=mode, cache=cache,
                                norm_eps=cfg.norm_eps,
                                use_kernel=rt.use_kernel and mode == "train")
    if kind == MAMBA2:
        return ssm.mamba2_apply(p, cfg, h, mode=mode, cache=cache,
                                norm_eps=cfg.norm_eps)
    raise ValueError(kind)


def _apply_group(rt: Runtime, pattern, gp, shared_p, h, *, mode, gcache,
                 pos, placement, token_mask=None, paged=None, origin=None):
    """Apply one scan group. Returns (h, new_gcache, moe_stats)."""
    new_cache = {}
    moe_stats = None
    for i, kind in enumerate(pattern):
        p = shared_p if kind == SHARED_ATTN else gp[f"b{i}"]
        c = gcache.get(f"b{i}") if gcache is not None else None
        h, extra = _apply_block(rt, kind, p, h, mode=mode, cache=c, pos=pos,
                                placement=placement, token_mask=token_mask,
                                paged=paged, origin=origin)
        if kind == MOE:
            moe_stats = extra  # <=1 MoE sublayer per group in all configs
        elif extra is not None:
            new_cache[f"b{i}"] = extra
    return h, new_cache, moe_stats


def _zero_moe_stats(rt: Runtime):
    cfg = rt.cfg
    n_ep = rt.ep_spec.n_ep if (rt.moe_impl == "ep" and rt.ep_spec) else 1
    return {"counts": jnp.zeros((cfg.num_experts,), jnp.float32),
            "counts_per_rank": jnp.zeros((n_ep, cfg.num_experts), jnp.float32),
            "aux_loss": jnp.float32(0.0),
            "local_frac": jnp.float32(0.0)}


def stack_placement(placement, n_groups: int):
    """Broadcast a single EPPlacement to the per-layer stacked form
    [n_groups, ...] consumed by the scan (per-layer tables may also be built
    directly by the placement algorithms)."""
    import jax.numpy as _jnp
    return jax.tree.map(
        lambda a: _jnp.broadcast_to(a, (n_groups,) + a.shape), placement)


def _run_stack(rt: Runtime, params, h, *, mode, cache, pos, placement,
               token_mask=None, paged=None, origin=None):
    """Scan the layer groups. Returns (h, new_cache, stacked_moe_stats).

    ``placement`` (EP MoE only): EPPlacement pytree with a leading
    [n_groups] dim — each scan step consumes its own layer's tables, which
    is how Algorithm 1's layer-wise expert-count allocation reaches the
    runtime. ``token_mask`` ([B] in decode, [B, T] in chunk mode) excludes
    vacant continuous-batching rows / prompt padding from the gating
    statistics. ``origin`` ([B] or [B, T] int32) attributes each token's
    gating counts to the EP rank its request originated at (Algorithm 1's
    per-server f_n(e)); without it counts fall back to the physical rank.
    ``paged`` (decode/chunk): the page-table info shared by all layers —
    every layer indexes the same physical block ids into its own pool."""
    cfg = rt.cfg
    pattern, n_groups = cfg.layer_pattern()
    shared_p = params.get("shared_attn")
    has_moe = MOE in pattern
    use_pl = has_moe and rt.moe_impl == "ep"
    if use_pl and placement is None:
        raise ValueError("EP MoE requires a placement")
    if rt.layout in ("sp", "cp", "fsdp") and rt.mesh is not None \
            and mode not in ("decode", "chunk"):
        h = _sp_scatter(rt, h)          # residual stream: seq over model

    def body(carry, xs):
        hh = carry
        gp, gcache, gpl = xs
        hh, new_gcache, mstats = _apply_group(
            rt, pattern, gp, shared_p, hh, mode=mode, gcache=gcache,
            pos=pos, placement=gpl, token_mask=token_mask, paged=paged,
            origin=origin)
        if mstats is None:
            mstats = _zero_moe_stats(rt)
        return hh, (new_gcache, mstats)

    if mode == "train":
        if rt.remat_policy == "dots":
            policy = jax.checkpoint_policies.dots_saveable
        elif rt.remat_policy == "dots+kv":
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_saveable,
                jax.checkpoint_policies.save_only_these_names("kv_gathered"))
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        body_fn = jax.checkpoint(body, policy=policy)
    else:
        body_fn = body
    pl_xs = placement if use_pl else None
    if not rt.scan_layers:
        caches_l, mstats_l = [], []
        for g in range(n_groups):
            take = lambda t: jax.tree.map(lambda a: a[g], t) \
                if t is not None else None
            h, (gc, ms) = body_fn(h, (take(params["groups"]), take(cache),
                                      take(pl_xs)))
            caches_l.append(gc)
            mstats_l.append(ms)
        new_caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches_l) \
            if caches_l and caches_l[0] else None
        mstats = jax.tree.map(lambda *xs: jnp.stack(xs), *mstats_l)
    elif cache is None:
        h, (new_caches, mstats) = lax.scan(
            lambda c, xs: body_fn(c, (xs[0], None, xs[1])),
            h, (params["groups"], pl_xs))
    else:
        h, (new_caches, mstats) = lax.scan(
            lambda c, xs: body_fn(c, xs),
            h, (params["groups"], cache, pl_xs))
    if not has_moe:
        mstats = None
    return h, (new_caches if cache is not None or mode == "prefill" else None), mstats


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def _embed(rt: Runtime, params, tokens):
    return params["embed"][tokens].astype(rt.dtype)


def _logits(rt: Runtime, params, h):
    with jax.named_scope("head"):
        h = rms_norm(h, params["final_norm"], rt.cfg.norm_eps)
        w = params["embed"].T if rt.cfg.tie_embeddings else params["lm_head"]
        return h @ w


def _chunked_xent(rt: Runtime, params, h, targets):
    """Cross-entropy with per-chunk logit remat (never materialises the full
    [B,T,V] logits)."""
    B, T, D = h.shape
    # NOTE (§Perf, refuted hypothesis): computing the loss unchunked on
    # (data x model)-sharded rows looked like it would remove the per-chunk
    # dynamic-slice all-gathers (~4 GB), but measured WORSE (43.3 vs 29.2 GB
    # collectives, +130 ms compute) — the flatten of two sharded dims
    # introduced a bigger reshard than the chunk scan. Chunked path kept.
    rows = h.reshape(B * T, D)
    tgt = targets.reshape(B * T)
    chunk = min(rt.loss_chunk, B * T)
    n = B * T // chunk
    rows = rows[:n * chunk].reshape(n, chunk, D)
    tgt_c = tgt[:n * chunk].reshape(n, chunk)

    @jax.checkpoint
    def chunk_loss(r, t):
        lg = _logits(rt, params, r)
        return softmax_xent(lg, t).sum()

    def body(acc, xs):
        r, t = xs
        return acc + chunk_loss(r, t), None

    total, _ = lax.scan(body, jnp.float32(0.0), (rows, tgt_c))
    return total / (n * chunk)


def loss_fn(rt: Runtime, params, tokens, targets, placement=None,
            aux_weight: float = 0.01):
    """tokens/targets: [B, T] int32. Returns (loss, metrics)."""
    h = _embed(rt, params, tokens)
    h, _, mstats = _run_stack(rt, params, h, mode="train", cache=None,
                              pos=None, placement=placement)
    if rt.layout in ("sp", "cp", "fsdp") and rt.mesh is not None:
        # one gather of h before the loss: the chunk scan then slices a
        # batch-only-sharded rows array (free) instead of re-gathering a
        # (batch x model)-sharded one per chunk (measured 5.4 GB/step)
        h = _sp_gather(rt, h)
        from repro.models import sharding as _shd
        b = tuple(a for a in rt.mesh.axis_names if a != "model")
        targets = _shd.constrain(rt.mesh, targets, P(b, None))
    ce = _chunked_xent(rt, params, h, targets)
    metrics = {"ce_loss": ce}
    loss = ce
    if mstats is not None:
        aux = mstats["aux_loss"].mean()
        loss = loss + aux_weight * aux
        metrics.update(aux_loss=aux,
                       local_frac=mstats["local_frac"].mean(),
                       expert_counts=mstats["counts_per_rank"])
    metrics["loss"] = loss
    return loss, metrics


def _constrain_outputs(rt: Runtime, logits, cache):
    if rt.mesh is None:
        return logits, cache
    from repro.models import sharding as sh
    b = tuple(a for a in rt.mesh.axis_names if a != "model")
    logits = sh.constrain(rt.mesh, logits, P(b, "model"))
    if cache is not None:
        specs = sh.cache_pspecs(rt, seq_sharded=rt.cache_seq_sharded)
        cache = sh.constrain(rt.mesh, cache, specs)
    return logits, cache


def prefill(rt: Runtime, params, tokens=None, embeds=None, placement=None,
            cache_len: int | None = None, origin=None):
    """Returns (last-token logits [B, V], cache, moe_stats). ``origin``:
    optional [B] int32 — the EP rank each request originated at (gating
    stats attribution; defaults to the physical row-sharding rank)."""
    h = _embed(rt, params, tokens) if embeds is None else embeds.astype(rt.dtype)
    B, T = h.shape[:2]
    cache = init_cache(rt, B, cache_len if cache_len is not None else T)
    h, new_cache, mstats = _run_stack(rt, params, h, mode="prefill",
                                      cache=cache, pos=None,
                                      placement=placement, origin=origin)
    logits = _logits(rt, params, h[:, -1])
    logits, new_cache = _constrain_outputs(rt, logits, new_cache)
    return logits, new_cache, mstats


def decode_step(rt: Runtime, params, cache, tokens, pos, placement=None,
                token_mask=None, page_table=None, origin=None):
    """tokens: [B, 1] int32; pos: scalar int32 (whole batch at one
    position) or [B] int32 vector (continuous batching: per-row positions).
    token_mask: optional [B] float validity — 0-rows (vacant pool slots)
    are excluded from the MoE gating statistics.
    page_table: optional [B, P] int32 — ``cache`` is then a paged block
    pool (``init_paged_cache``) and each row reads/writes through its pages.
    origin: optional [B] int32 originating EP rank per row (stats
    attribution).
    Returns (logits [B, V], new_cache, moe_stats).

    Donation-safe: ``new_cache`` is a pure functional ``.at[].set()``
    update of ``cache`` with identical shapes/dtypes per leaf, so callers
    may jit/AOT-compile with the cache donated (``donate_argnums``) and
    XLA aliases the update in place — the serving engine's zero-stall
    decode path relies on this (no per-step pool allocation). Never return
    a leaf whose shape/dtype differs from its input."""
    h = _embed(rt, params, tokens)
    paged = {"page_table": page_table} if page_table is not None else None
    h, new_cache, mstats = _run_stack(rt, params, h, mode="decode",
                                      cache=cache, pos=pos,
                                      placement=placement,
                                      token_mask=token_mask, paged=paged,
                                      origin=origin)
    logits = _logits(rt, params, h[:, -1])
    if page_table is not None:
        # paged pools have block-major shapes the dense cache pspecs don't
        # describe; serving runs single-host, so constrain logits only
        logits, _ = _constrain_outputs(rt, logits, None)
        return logits, new_cache, mstats
    logits, new_cache = _constrain_outputs(rt, logits, new_cache)
    return logits, new_cache, mstats


def prefill_chunk(rt: Runtime, params, cache, tokens, page_table,
                  write_blocks, offset, last_idx, placement=None,
                  token_mask=None, origin=None):
    """Batched paged chunked prefill: consume one ``block_size``-aligned
    chunk of up to ``B`` *different* prompts (one per serving slot) into a
    paged pool in a single call, so short non-shared prompt tails don't
    serialize behind each other.

    tokens: [B, bs] int32 — row ``b`` is one whole-block chunk of slot
    ``b``'s prompt (the tail beyond the true prompt is padding — mask it
    via ``token_mask``; rows of idle slots are all-padding).
    page_table: [B, P] — each slot's full page table (logical order).
    write_blocks: [B] int32 — the physical block receiving each row's k/v
    (idle rows target the reserved null block 0).
    offset: [B] int32 — absolute position of ``tokens[b, 0]``.
    last_idx: [B] int32 — in-chunk index whose logits to return per row
    (the final prompt token on a last chunk; ignored otherwise).
    token_mask: optional [B, bs] float — 0 for padding tokens (excluded
    from the MoE gating statistics).
    origin: optional [B] int32 originating EP rank per row.
    Returns (logits [B, V], new_cache, moe_stats).

    Donation-safe like ``decode_step``: every ``new_cache`` leaf is a
    same-shape functional update of the input pool, so the chunked-prefill
    executables compile with the pool donated."""
    h = _embed(rt, params, tokens)
    paged = {"page_table": page_table, "write_blocks": write_blocks}
    h, new_cache, mstats = _run_stack(rt, params, h, mode="chunk",
                                      cache=cache, pos=offset,
                                      placement=placement,
                                      token_mask=token_mask, paged=paged,
                                      origin=origin)
    B = h.shape[0]
    h_last = h[jnp.arange(B), jnp.asarray(last_idx)]       # [B, D]
    logits = _logits(rt, params, h_last)
    logits, _ = _constrain_outputs(rt, logits, None)
    return logits, new_cache, mstats


def copy_paged_block(pool, src, dst):
    """Copy one physical block across every layer of a paged pool (the
    serving-side copy-on-write primitive: clone a shared tail block before
    a sharer's first write). ``pool`` is the ``init_paged_cache`` pytree
    (leading n_groups dim per layer); src/dst are scalar block ids.
    Donation-safe (same-shape functional update): the engine AOT-compiles
    it with the pool donated so CoW clones allocate nothing."""
    return {k: attn.copy_pool_block(c, src, dst, block_axis=1)
            for k, c in pool.items()}


def supports_paging(rt: Runtime) -> bool:
    """Whether this runtime's caches can live in a paged block pool:
    attention-only state (no SSM recurrence), no sliding-window ring, and
    no sequence-sharded cache (the paged paths don't constrain block-pool
    shardings — a seq-sharded pool would silently reshard every step).
    Pure metadata — no allocation."""
    pattern, _ = rt.cfg.layer_pattern()
    return (not rt.window and not rt.cache_seq_sharded
            and not any(k in (MAMBA1, MAMBA2) for k in pattern))


def init_paged_cache(rt: Runtime, n_blocks: int, block_size: int,
                     dtype=None) -> dict:
    """Paged KV block pool: per attention group, ``[n_groups, n_blocks,
    block_size, KVH, hd]`` shared by all serving slots (block 0 reserved as
    the null block). Raises for architectures whose caches cannot be paged
    (see ``supports_paging``)."""
    if dtype is None:
        dtype = rt.dtype
    cfg = rt.cfg
    if not supports_paging(rt):
        raise ValueError(
            "paged KV pool requires attention-only caches without a "
            f"sliding window; {cfg.name} (window={rt.window}) does not "
            "qualify — use the dense slot pool")
    pattern, n_groups = cfg.layer_pattern()
    out = {}
    for i, kind in enumerate(pattern):
        if kind not in (ATTN, SHARED_ATTN):
            continue
        c = attn.init_paged_kv(cfg, n_blocks, block_size, dtype=dtype,
                               quantized=rt.kv_quant)
        out[f"b{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), c)
    return out


def init_cache(rt: Runtime, batch: int, seq_len: int,
               dtype=None) -> dict:
    if dtype is None:
        dtype = rt.dtype
    """Per-group cache pytree, leading dim = n_groups (stacked for scan)."""
    cfg = rt.cfg
    pattern, n_groups = cfg.layer_pattern()
    out = {}
    for i, kind in enumerate(pattern):
        if kind in (ATTN, SHARED_ATTN):
            c = attn.init_attn_cache(cfg, batch, seq_len, window=rt.window,
                                     dtype=dtype, quantized=rt.kv_quant)
        elif kind == MAMBA1:
            c = ssm.init_mamba1_cache(cfg, batch, dtype)
        elif kind == MAMBA2:
            c = ssm.init_mamba2_cache(cfg, batch, dtype)
        else:
            continue
        out[f"b{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), c)
    return out
