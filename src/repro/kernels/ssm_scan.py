"""Pallas TPU kernel: fused Mamba-1 selective scan.

Computes y directly from (x, dt, B, C, A, D) without ever materialising the
[B, T, d, N] state trajectory in HBM — the state h lives in a fp32 VMEM
scratch that persists across the sequential T grid dimension. The decay
a_t = exp(dt_t * A) and input b_t = (dt_t * x_t) B_t are formed on the fly
per time step inside the kernel (VPU elementwise + small outer products).

Layout: the state is held transposed, ``h [N, bd]``, so channels sit on the
lanes. The wrapper gives every per-step operand a leading time axis of its
own (x, dt, y as ``[B, T, 1, d]`` rows; B_t and C_t as ``[B, T, N, 1]``
columns), so the loop reads step ``t`` with a dynamic index on an untiled
dimension — the only dynamic indexing Mosaic lowers — and no value is ever
sliced or transposed inside the kernel.

Grid: (B, d/bd, T/bt) with T innermost/sequential; channels are
embarrassingly parallel (and shard over the `model` mesh axis one level up).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, o_ref, h_ref, *,
            bt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    At = a_ref[...].astype(jnp.float32)           # [N, bd]
    Dp = d_ref[...].astype(jnp.float32)           # [1, bd]

    def step(t, h):
        x_t = x_ref[0, t].astype(jnp.float32)     # [1, bd]
        dt_t = dt_ref[0, t].astype(jnp.float32)   # [1, bd]
        b_t = b_ref[0, t].astype(jnp.float32)     # [N, 1]
        c_t = c_ref[0, t].astype(jnp.float32)     # [N, 1]
        h = jnp.exp(dt_t * At) * h + (dt_t * x_t) * b_t
        y_t = (h * c_t).sum(0, keepdims=True) + Dp * x_t
        o_ref[0, t] = y_t.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bt, step, h_ref[...])


@functools.partial(jax.jit, static_argnames=("bd", "bt", "interpret"))
def ssm_scan(x, dt, Bs, Cs, A, D, *, bd: int = 256, bt: int = 64,
             interpret: bool | None = None):
    """x, dt: [B, T, d]; Bs, Cs: [B, T, N]; A: [d, N]; D: [d] -> y [B, T, d]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, T, d = x.shape
    N = Bs.shape[-1]
    bd = min(bd, d)
    bt = min(bt, T)
    assert d % bd == 0 and T % bt == 0
    grid = (B, d // bd, T // bt)
    row = pl.BlockSpec((1, bt, 1, bd), lambda b, i, t: (b, t, 0, i))
    col = pl.BlockSpec((1, bt, N, 1), lambda b, i, t: (b, t, 0, 0))
    y = pl.pallas_call(
        functools.partial(_kernel, bt=bt),
        grid=grid,
        in_specs=[
            row,                                                 # x
            row,                                                 # dt
            col,                                                 # B
            col,                                                 # C
            pl.BlockSpec((N, bd), lambda b, i, t: (0, i)),       # A^T
            pl.BlockSpec((1, bd), lambda b, i, t: (0, i)),       # D
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((B, T, 1, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x[:, :, None], dt[:, :, None], Bs[..., None], Cs[..., None], A.T,
      D[None])
    return y[:, :, 0]
