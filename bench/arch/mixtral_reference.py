"""Plain float32 reference of the Mixtral-style decoder
(``bench/arch/mixtral.py``), and its lower-precision control.

A Mixtral-style decoder written out in ``jax.numpy`` from the published
description (RMSNorm, rotary embeddings on the two halves of each head,
grouped-query causal attention, softmax top-k routing renormalised over
the chosen experts, SwiGLU experts), with every matmul at
``Precision.HIGHEST``. It imports nothing of the program: it reads its
weights from the same seed through ``bench.weights``, one layer at a time,
so that it fits beside nothing else on one chip.

The control is the same forward with every weight matmul through
``bench.compare.matmul``'s float8 rule.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.compare import HI
from bench.compare import matmul as _mm


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x [L, H, hd]: rotate the two halves of each head by position."""
    L, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _attention(h, lw, dims, quant: bool):
    """Causal GQA self-attention of one sequence h [L, d]."""
    L = h.shape[0]
    x = _rms(h, lw["attn_norm"], dims.eps)
    q = _mm(x, lw["wq"], quant).reshape(L, dims.heads, dims.hd)
    k = _mm(x, lw["wk"], quant).reshape(L, dims.kv_heads, dims.hd)
    v = _mm(x, lw["wv"], quant).reshape(L, dims.kv_heads, dims.hd)
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    grp = dims.heads // dims.kv_heads
    q = q.reshape(L, dims.kv_heads, grp, dims.hd)
    s = jnp.einsum("qkgd,tkd->kgqt", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(dims.hd))
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)
    return _mm(o.reshape(L, dims.heads * dims.hd), lw["wo"], quant)


def _moe(h, lw, dims, quant: bool):
    """Top-k softmax routing over every expert, computed densely. Also
    returns each token's routing margin: how far the k-th expert's
    probability lies above the next one's."""
    x = _rms(h, lw["moe_norm"], dims.eps)
    probs = jax.nn.softmax(_mm(x, lw["router"], quant), axis=-1)
    ranked = jax.lax.top_k(probs, dims.top_k + 1)[0]
    margin = ranked[:, -2] - ranked[:, -1]
    topv, topi = jax.lax.top_k(probs, dims.top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], topi].set(topv)
    y = jnp.zeros_like(x)
    for e in range(dims.experts):
        a = _mm(x, lw["w1"][e], quant)
        b = _mm(x, lw["w3"][e], quant)
        y = y + gate[:, e:e + 1] * _mm(jax.nn.silu(a) * b, lw["w2"][e], quant)
    return y, margin


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(h, lw, dims, quant: bool):
    """One decoder layer over a batch of padded sequences h [K, L, d]
    (padding sits after each sequence, so causality keeps it out); also
    the routing margin of every position [K * L]."""
    h = h + jax.lax.map(lambda s: _attention(s, lw, dims, quant), h)
    K, L, d = h.shape
    flat = h.reshape(K * L, d)
    y, margin = _moe(flat, lw, dims, quant)
    return (flat + y).reshape(K, L, d), margin


@functools.partial(jax.jit, static_argnums=(1,))
def _gen_layer(key, dims, layer):
    return W.layer_leaves(key, dims, layer)


@functools.partial(jax.jit, static_argnums=(1,))
def _gen_head(key, dims):
    return {n: W.leaf(key, dims, n, 0) for n in ("embed", "final_norm", "lm_head")}


@functools.partial(jax.jit, static_argnums=(4, 5))
def _logits(h, rows, final_norm, lm_head, dims, quant: bool):
    x = _rms(h.reshape(-1, h.shape[-1])[rows], final_norm, dims.eps)
    return _mm(x, lm_head, quant)


def forward_logits(dims, seed: int, seqs, length: int, rows: int,
                   control: bool = False, device=None):
    """Logits of the positions that predict each served token.

    seqs: ``(prompt, served)`` pairs. Each sequence ``prompt + served[:-1]``
    is padded to ``length`` and the logits at positions ``len(prompt) - 1
    ...`` are returned as one float32 array [n_served, V] (the rows padded
    to ``rows`` for a fixed shape, then cut). With ``control`` the float8
    forward runs beside it and its logits come second (else None). Third:
    each of those positions' smallest routing margin over the layers (the
    reference's; a token near a tie may go to another expert in bfloat16)."""
    K = len(seqs)
    toks = np.zeros((K, length), np.int32)
    idx = []
    for i, (prompt, served) in enumerate(seqs):
        s = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        toks[i, :len(s)] = s
        idx.extend(i * length + len(prompt) - 1 + np.arange(len(served)))
    n = len(idx)
    if n > rows:
        raise ValueError(f"{n} served tokens exceed the {rows} reference rows")
    sel = np.zeros(rows, np.int32)
    sel[:n] = idx
    key = W.base_key(seed)
    put = (lambda a: jax.device_put(a, device)) if device is not None else \
        (lambda a: a)
    head = _gen_head(key, dims)
    h = put(head["embed"])[put(toks)].astype(jnp.float32)
    hc = h if control else None
    margin = None
    for layer in range(dims.layers):
        lw = _gen_layer(key, dims, layer)
        h, m = _layer(h, lw, dims, False)
        margin = m if margin is None else jnp.minimum(margin, m)
        if control:
            hc, _ = _layer(hc, lw, dims, True)
        del lw
    sel = put(sel)
    ref = np.asarray(_logits(h, sel, head["final_norm"], head["lm_head"], dims,
                             False))[:n]
    ctl = None
    if control:
        ctl = np.asarray(_logits(hc, sel, head["final_norm"], head["lm_head"],
                                 dims, True))[:n]
    return ref, ctl, np.asarray(margin)[np.asarray(idx)]

