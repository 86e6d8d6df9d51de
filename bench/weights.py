"""Seeded weights of a configuration, made on the device.

Every leaf is drawn from its own key, ``fold_in(fold_in(base, leaf), layer)``,
so the served model (all layers stacked, made in one jitted call) and the
reference (one layer at a time) read bit-identical values. Weights are made
in bfloat16, the type they are served in.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# leaf name -> index in the key derivation (append only: an index is part
# of what a seed means)
LEAVES = ("embed", "lm_head", "final_norm", "attn_norm", "wq", "wk", "wv",
          "wo", "moe_norm", "router", "w1", "w3", "w2")
NORMS = ("final_norm", "attn_norm", "moe_norm")
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "moe_norm", "router",
                "w1", "w3", "w2")
DTYPE = jnp.bfloat16


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a Mixtral-style decoder, read from a configuration
    file with Hugging Face key names."""

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

    @staticmethod
    def from_config(c: dict) -> "Dims":
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


def base_key(seed: int):
    """A key from any non-negative whole number (more than 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def leaf(key, dims: Dims, name: str, layer):
    """One leaf: norms near 1, embeddings and the head at 0.02, every
    projection normal over the square root of its fan-in."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAVES.index(name)), layer)
    shape = dims.shape(name)
    z = jax.random.normal(k, shape, jnp.float32)
    if name in NORMS:
        w = 1.0 + 0.1 * z
    elif name in ("embed", "lm_head"):
        w = 0.02 * z
    else:
        w = z * shape[-2] ** -0.5
    return w.astype(DTYPE)


def stacked(key, dims: Dims, name: str):
    """A per-layer leaf for every layer, stacked on a leading axis."""
    return jnp.stack([leaf(key, dims, name, layer) for layer in range(dims.layers)])


def layer_leaves(key, dims: Dims, layer) -> dict:
    """Every leaf of one layer (``layer`` may be traced)."""
    return {n: leaf(key, dims, n, layer) for n in LAYER_LEAVES}
