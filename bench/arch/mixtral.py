"""A Mixtral-style decoder: grouped-query attention with rotary
embeddings and a softmax top-k MoE of SwiGLU experts in every layer.
Mixtral-8x7B and Phi-3.5-MoE (as this benchmark runs it) name it.

What an architecture module gives the harness, found by the path in a
configuration's ``"arch"`` key:

    dims(conf)                 the sizes, read from the configuration's own
                               keys; the object carries the leaf table
                               (``bench/weights.py``) and ``layers``
    model_config(conf, name)   the program's ``ModelConfig``
    params(key, dims, mesh)    the program's parameter tree, made in one
                               jitted call, and the sharding of its expert
                               weights
    work(dims, kind, call, block_size)
                               the needed work of one ``decode`` or
                               ``chunk`` call, as ``{"step": {...},
                               <program scope>: {...}}``, each with
                               ``flops`` and ``bytes``
    TINY                       the configuration keys cut for a CPU test
                               (``bench/tiny.py``)
    LAYOUT (optional)          where the program's step keeps the
                               arguments ``work`` reads (the harness's
                               ``DEFAULT_LAYOUT`` where absent)

Counts are of what a step serves: the live rows, their real context, and
the experts the step's routing touched. Padded rows, the EP layer's
padded expert buffers and the width of the page table never enter a
count, so a roofline share computed from these stays at or under 100%
whatever padding the program carries. All counts are for the whole step
(every chip together), in bfloat16 weights and KV (2 bytes a value).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import weights as W

BYTES = 2  # bfloat16
# the published vocabulary and expert count stay: a float8 control
# departs from the reference through near-ties among many logits
TINY = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2}


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of the decoder, with Hugging Face key names read."""

    d: int
    heads: int
    kv_heads: int
    hd: int
    f: int
    experts: int
    top_k: int
    vocab: int
    layers: int
    rope_theta: float
    eps: float

    # leaf name -> kind, in key order (append only: see bench/weights.py)
    LEAVES = {"embed": "embed", "lm_head": "embed", "final_norm": "norm",
              "attn_norm": "norm", "wq": "proj", "wk": "proj", "wv": "proj",
              "wo": "proj", "moe_norm": "norm", "router": "proj",
              "w1": "proj", "w3": "proj", "w2": "proj"}
    LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "moe_norm", "router",
                    "w1", "w3", "w2")

    def shape(self, name: str) -> tuple:
        d, q, kv = self.d, self.heads * self.hd, self.kv_heads * self.hd
        return {
            "embed": (self.vocab, d),
            "lm_head": (d, self.vocab),
            "final_norm": (d,),
            "attn_norm": (d,),
            "wq": (d, q),
            "wk": (d, kv),
            "wv": (d, kv),
            "wo": (q, d),
            "moe_norm": (d,),
            "router": (d, self.experts),
            "w1": (self.experts, d, self.f),
            "w3": (self.experts, d, self.f),
            "w2": (self.experts, self.f, d),
        }[name]


def dims(c: dict) -> Dims:
    heads = c["num_attention_heads"]
    return Dims(
        d=c["hidden_size"],
        heads=heads,
        kv_heads=c["num_key_value_heads"],
        hd=c.get("head_dim") or c["hidden_size"] // heads,
        f=c["intermediate_size"],
        experts=c["num_local_experts"],
        top_k=c["num_experts_per_tok"],
        vocab=c["vocab_size"],
        layers=c["num_hidden_layers"],
        rope_theta=float(c["rope_theta"]),
        eps=float(c["rms_norm_eps"]),
    )


def model_config(conf: dict, name: str):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    d = dims(conf)
    return ModelConfig(
        name=name, family="moe", num_layers=d.layers, d_model=d.d,
        num_heads=d.heads, num_kv_heads=d.kv_heads, d_ff=d.f,
        vocab_size=d.vocab, head_dim=d.hd, num_experts=d.experts,
        top_k=d.top_k, moe_every=1, rope_theta=d.rope_theta,
        norm_eps=d.eps, source=conf["source"],
    )


def params(key, dims: Dims, mesh):
    """The program's parameter tree (dense master experts), made in one
    jitted call from ``key``; expert weights sharded over ``model``."""

    def make(k):
        groups = {
            "b0": {
                "norm": W.stacked(k, dims, "attn_norm"),
                **{n: W.stacked(k, dims, n) for n in ("wq", "wk", "wv", "wo")},
            },
            "b1": {
                "norm": W.stacked(k, dims, "moe_norm"),
                **{n: W.stacked(k, dims, n) for n in ("router", "w1", "w3", "w2")},
            },
        }
        return {
            "embed": W.leaf(k, dims, "embed", 0),
            "final_norm": W.leaf(k, dims, "final_norm", 0),
            "lm_head": W.leaf(k, dims, "lm_head", 0),
            "groups": groups,
        }

    rep = NamedSharding(mesh, P())
    experts = NamedSharding(mesh, P(None, "model"))
    shapes = jax.eval_shape(make, key)
    out = jax.tree.map(lambda _: rep, shapes)
    for w in ("w1", "w3", "w2"):
        out["groups"]["b1"][w] = experts
    return jax.jit(make, out_shardings=out)(key), experts


# -- needed work ---------------------------------------------------------

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
    ctx = _chunk_ctx(offsets, valid)
    finals = int(np.sum(valid < block_size))
    flops = token_flops(c, ctx) + finals * 2 * c.d * c.vocab
    byts = (_weight_bytes(c, experts_touched, int(valid.sum()))
            + kv_bytes(c, offsets) + kv_bytes(c, valid))
    return {"flops": flops, "bytes": byts, "tokens": int(valid.sum())}


def _chunk_ctx(offsets, valid) -> np.ndarray:
    """Keys each prompt token of a chunk attends over."""
    return (np.concatenate([o + 1 + np.arange(v) for o, v in zip(offsets, valid)])
            if len(valid) else np.zeros(0))


def attention(c: Dims, ctx: np.ndarray, read, write) -> dict:
    """The ``attn`` scope over every layer: the q, k, v and o projections,
    scores and values over ``ctx`` keys a token, and the K/V cache
    entries it reads (``read``) and writes (``write``)."""
    ctx = np.asarray(ctx, np.float64)
    flops = c.layers * (2 * attn_weight_params(c) * ctx.size
                        + 4 * c.heads * c.hd * ctx.sum())
    byts = (BYTES * c.layers * attn_weight_params(c)
            + kv_bytes(c, read) + kv_bytes(c, write))
    return {"flops": float(flops), "bytes": byts}


def expert_ffn(c: Dims, tokens: int, experts_touched: np.ndarray) -> dict:
    """The ``moe.expert_ffn`` scope over every layer: each token's top-k
    SwiGLU experts, reading the touched experts' weights and each routed
    row in and out."""
    flops = c.layers * tokens * c.top_k * 2 * expert_params(c)
    byts = BYTES * (float(np.sum(experts_touched)) * expert_params(c)
                    + c.layers * tokens * c.top_k * 2 * c.d)
    return {"flops": float(flops), "bytes": byts}


def work(c: Dims, kind: str, call: dict, block_size: int) -> dict:
    """Needed work of one recorded call: ``call`` holds the step's live
    row mask (``mask``), positions (``pos``, decode) or chunk offsets
    (``offsets``, chunk), and gating ``counts`` [layers, experts]."""
    touched = (np.asarray(call["counts"]) > 0).sum(-1)
    if kind == "decode":
        live = np.asarray(call["mask"]) > 0
        ctx = np.asarray(call["pos"])[live] + 1
        return {"step": decode_step(c, ctx, touched),
                "attn": attention(c, ctx, ctx - 1, ctx.size),
                "moe.expert_ffn": expert_ffn(c, ctx.size, touched)}
    valid = np.asarray(call["mask"]).sum(-1).astype(int)
    live = valid > 0
    offsets = np.asarray(call["offsets"])[live]
    valid = valid[live]
    return {"step": prefill_chunk(c, offsets, valid, touched, block_size),
            "attn": attention(c, _chunk_ctx(offsets, valid), offsets, valid),
            "moe.expert_ffn": expert_ffn(c, int(valid.sum()), touched)}
