"""Pallas TPU kernel: grouped expert FFN (the MoE compute hot-spot).

Computes, per expert slot s:  y[s] = (silu(x[s] @ w1[s]) * (x[s] @ w3[s])) @ w2[s]

Grid: (S, C/bc, F/bf) with the F dimension innermost/sequential — each step
loads one [D, bf] tile of w1/w3 and one [bf, D] tile of w2 into VMEM,
accumulating the output tile in a fp32 VMEM scratch (classic K-blocked
matmul with the gated nonlinearity fused between the two matmuls, so the
[C, F] intermediate never touches HBM).

Tiling: bc x bf blocks are MXU-aligned (multiples of 128 whenever the
problem shape allows); D stays resident per block. ``bf`` is the largest
multiple of 128 no larger than the requested width that divides F (F = 1408
for DeepSeek-V2-Lite and 6400 for Phi-3.5-MoE are not multiples of 512), and
the scoped VMEM limit is raised to what the double-buffered blocks plus the
fp32 accumulator need: at Mixtral width (D 4096, bf 512) that is ~30 MiB,
over the compiler's 16 MiB default but well inside v5e's 128 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w1_ref, w3_ref, w2_ref, o_ref, acc_ref, *, nf: int):
    f = pl.program_id(2)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]                                   # [bc, D]
    a = jnp.dot(x, w1_ref[0], preferred_element_type=jnp.float32)
    b = jnp.dot(x, w3_ref[0], preferred_element_type=jnp.float32)
    mid = (jax.nn.silu(a) * b).astype(x.dtype)     # [bc, bf]
    acc_ref[...] += jnp.dot(mid, w2_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(f == nf - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _f_block(F: int, bf: int) -> int:
    """Largest multiple of 128 <= ``bf`` dividing F (F itself when F is not
    a multiple of 128: a block may always span the whole dimension)."""
    if F % 128:
        return F
    units = F // 128
    return 128 * max(u for u in range(1, max(bf // 128, 1) + 1)
                     if units % u == 0)


def _vmem_bytes(bc: int, bf: int, D: int, itemsize: int) -> int:
    """Scoped VMEM the kernel needs: every in/out block double-buffered
    (x and out [bc, D], w1/w3 [D, bf], w2 [bf, D]) plus the fp32
    accumulator."""
    blocks = (2 * bc * D + 3 * D * bf) * itemsize
    return 2 * blocks + 4 * bc * D


@functools.partial(jax.jit, static_argnames=("bc", "bf", "interpret"))
def moe_gmm(x, w1, w3, w2, *, bc: int = 128, bf: int = 512,
            interpret: bool | None = None):
    """x: [S, C, D]; w1/w3: [S, D, F]; w2: [S, F, D] -> [S, C, D]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    S, C, D = x.shape
    F = w1.shape[-1]
    bc = min(bc, C)
    bf = _f_block(F, min(bf, F))
    assert C % bc == 0, (C, bc)
    vmem = _vmem_bytes(bc, bf, D, x.dtype.itemsize)
    nf = F // bf
    grid = (S, C // bc, nf)
    return pl.pallas_call(
        functools.partial(_kernel, nf=nf),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, D), lambda s, c, f: (s, c, 0)),
            pl.BlockSpec((1, D, bf), lambda s, c, f: (s, 0, f)),
            pl.BlockSpec((1, D, bf), lambda s, c, f: (s, 0, f)),
            pl.BlockSpec((1, bf, D), lambda s, c, f: (s, f, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, D), lambda s, c, f: (s, c, 0)),
        out_shape=jax.ShapeDtypeStruct((S, C, D), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem + (4 << 20)),
        interpret=interpret,
    )(x, w1, w3, w2)
