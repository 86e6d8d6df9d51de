"""The comparison that decides ``correct``, apart from any one
architecture's reference.

A reference module (the path in a configuration's ``"reference"`` key)
computes, in float32, the logits at the positions that predict each
served token, and with ``control`` the same forward with every weight
matmul through ``matmul(..., control=True)``: the float8 step below
bfloat16 that would tempt a later change. Both are read here the same
way, as ``gaps``, and held to the configuration's ``limits``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def matmul(x, w, control: bool):
    """x [..., k] @ w [k, n] in float32, or, for the control, through
    float8 (e4m3, scaled per row of the activations and per column of the
    weights)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if not control:
        return jnp.matmul(x, w, precision=HI)
    xs = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / F8_MAX + 1e-30
    ws = jnp.max(jnp.abs(w), axis=0, keepdims=True) / F8_MAX + 1e-30
    xq = (x / xs).astype(F8).astype(jnp.float32)
    wq = (w / ws).astype(F8).astype(jnp.float32)
    return jnp.matmul(xq, wq, precision=HI) * xs * ws


def gaps(ref: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best, in standard deviations of the reference's logits at that step
    (so that the reading does not scale with the width of the model)."""
    best = ref.max(axis=-1)
    return (best - ref[np.arange(len(tokens)), tokens]) / ref.std(axis=-1)


def limited(conf: dict, gaps: np.ndarray) -> dict:
    """Each number the configuration limits, read from per-token gaps,
    beside its limit."""
    readings = {"max_gap": float(gaps.max()), "mean_gap": float(gaps.mean())}
    return {k: {"value": readings[k], "limit": lim}
            for k, lim in conf["limits"].items()}


def passes(checks: dict) -> bool:
    """The decision on ``correct``: every number within its limit."""
    return bool(checks) and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
