"""Operations and bytes that one serving step needs, counted from what it
serves: the live rows, their real context, and the experts the step's
routing touched. Padded rows, the EP layer's padded expert buffers and
the width of the page table never enter a count, so a roofline share computed from these stays
at or under 100% whatever padding the program carries.

All counts are for the whole step (every chip together), in bfloat16
weights and KV (2 bytes a value).
"""

from __future__ import annotations

import numpy as np

from bench.weights import Dims

BYTES = 2  # bfloat16


def attn_weight_params(c: Dims) -> int:
    q, kv = c.heads * c.hd, c.kv_heads * c.hd
    return c.d * (q + 2 * kv) + q * c.d


def expert_params(c: Dims) -> int:
    return 3 * c.d * c.f


def token_flops(c: Dims, ctx: np.ndarray) -> float:
    """FLOPs of every layer for tokens that attend over ``ctx`` keys each:
    projections, attention scores and values, the router and the top-k
    experts."""
    ctx = np.asarray(ctx, np.float64)
    per_token = (2 * attn_weight_params(c) + 2 * c.d * c.experts
                 + c.top_k * 2 * expert_params(c))
    attn = 4 * c.heads * c.hd * ctx
    return float(c.layers * (per_token * ctx.size + attn.sum()))


def _weight_bytes(c: Dims, experts_touched: np.ndarray, live_rows: int) -> float:
    per_layer = attn_weight_params(c) + c.d * c.experts + 2 * c.d
    touched = float(np.sum(experts_touched)) * expert_params(c)
    head = c.d * c.vocab + c.d
    embed_rows = live_rows * c.d
    return BYTES * (c.layers * per_layer + touched + head + embed_rows)


def kv_bytes(c: Dims, positions) -> float:
    """Bytes of K and V for ``positions`` cache entries over all layers."""
    return float(BYTES * 2 * c.kv_heads * c.hd * c.layers * np.sum(positions))


def decode_step(c: Dims, ctx: np.ndarray, experts_touched: np.ndarray) -> dict:
    """One decode call. ctx: [live rows] keys each row attends over (its
    position + 1, the new token included); experts_touched: [layers]
    distinct experts the call's routing chose."""
    ctx = np.asarray(ctx)
    n = ctx.size
    flops = token_flops(c, ctx) + n * 2 * c.d * c.vocab
    byts = (_weight_bytes(c, experts_touched, n)
            + kv_bytes(c, ctx - 1) + kv_bytes(c, n))
    return {"flops": flops, "bytes": byts, "tokens": n}


def prefill_chunk(c: Dims, offsets: np.ndarray, valid: np.ndarray,
                  experts_touched: np.ndarray, block_size: int) -> dict:
    """One chunked-prefill call. offsets/valid: [live rows] first position
    and prompt tokens of each row's chunk. Logits are counted only for rows
    whose chunk ends short of a full block (those certainly end a prompt)."""
    offsets = np.asarray(offsets, np.int64)
    valid = np.asarray(valid, np.int64)
    ctx = np.concatenate([o + 1 + np.arange(v) for o, v in zip(offsets, valid)]
                         ) if len(valid) else np.zeros(0)
    finals = int(np.sum(valid < block_size))
    flops = token_flops(c, ctx) + finals * 2 * c.d * c.vocab
    byts = (_weight_bytes(c, experts_touched, int(valid.sum()))
            + kv_bytes(c, offsets) + kv_bytes(c, valid))
    return {"flops": flops, "bytes": byts, "tokens": int(valid.sum())}


def roofline(work: dict, seconds: float, peaks: dict, chips: int) -> dict:
    """Least time over measured time, and which bound sets the least time."""
    t_flops = work["flops"] / (peaks["bf16_flops"] * chips)
    t_bytes = work["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    least = max(t_flops, t_bytes)
    return {"share": least / seconds,
            "bound": "flops" if t_flops >= t_bytes else "hbm"}
