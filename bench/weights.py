"""Seeded weights of a configuration, made on the device.

Every leaf is drawn from its own key, ``fold_in(fold_in(base, leaf), layer)``,
so the served model (all layers stacked, made in one jitted call) and the
reference (one layer at a time) read bit-identical values. Weights are made
in bfloat16, the type they are served in.

The leaves are the architecture's: its dims object (``dims(conf)`` of the
module a configuration's ``arch`` names) carries

    LEAVES        {leaf name: kind}, in key order. A leaf's index in it is
                  part of what a seed means, so the table is append only.
                  Kinds: ``norm`` (1 + 0.1 z), ``embed`` (0.02 z) and
                  ``proj`` (z over the square root of its fan-in, the
                  shape's second to last size)
    LAYER_LEAVES  the names drawn once per layer
    shape(name)   each leaf's shape
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


def base_key(seed: int):
    """A key from any non-negative whole number (more than 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def leaf(key, dims, name: str, layer):
    """One leaf of ``dims``'s table, drawn by its kind."""
    k = jax.random.fold_in(
        jax.random.fold_in(key, list(dims.LEAVES).index(name)), layer)
    shape = dims.shape(name)
    z = jax.random.normal(k, shape, jnp.float32)
    kind = dims.LEAVES[name]
    if kind == "norm":
        w = 1.0 + 0.1 * z
    elif kind == "embed":
        w = 0.02 * z
    elif kind == "proj":
        w = z * shape[-2] ** -0.5
    else:
        raise ValueError(f"leaf {name!r} has no known kind: {kind!r}")
    return w.astype(DTYPE)


def stacked(key, dims, name: str):
    """A per-layer leaf for every layer, stacked on a leading axis."""
    return jnp.stack([leaf(key, dims, name, layer) for layer in range(dims.layers)])


def layer_leaves(key, dims, layer) -> dict:
    """Every leaf of one layer (``layer`` may be traced)."""
    return {n: leaf(key, dims, n, layer) for n in dims.LAYER_LEAVES}
