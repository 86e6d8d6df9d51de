"""The EP layer sizes its send and slot buffers to the rows each call
carries: the spec's capacities are upper bounds, so a spec four times the
row bound computes the same buffers and gives the same result."""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.models import moe as M

CFG = get_config("mixtral-8x7b").reduced()
B, T = 4, 8          # 32 rows: a multiple of the sublane tile
MODES = ("decode", "dispatch", "body3")


def _spec(mesh, scale: int):
    rows = B * T
    return M.EPSpec.build(mesh, CFG, ep_axes=("model",),
                          slots=CFG.num_experts,
                          capacity=scale * rows * CFG.top_k,
                          slot_capacity=scale * rows)


def _ffn_operands(jaxpr, S, D, out):
    """The [S, C2, D] left operands of the grouped FFN's two input dots
    (``w1``, ``w3``) in ``jaxpr`` and the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            if len(lhs) == 3 and lhs[0] == S and lhs[2] == D \
                    and rhs[:2] == (S, D):
                out.append(lhs)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _ffn_operands(inner, S, D, out)
    return out


def _apply(mode, spec, p, pl, x, mask):
    mesh = make_test_mesh(1, 1)
    if mode == "decode":
        kw = dict(mode="decode", token_mask=mask[:, 0])
    elif mode == "dispatch":
        kw = dict(mode="chunk", token_mask=mask)
    else:
        kw = dict(mode="prefill", seq_sharded_out=True)

    def f(p, x):
        return M.moe_apply_ep(p, CFG, x, mesh=mesh, spec=spec, placement=pl,
                              **kw)
    return jax.jit(f)(p, x), jax.make_jaxpr(f)(p, x).jaxpr


@pytest.mark.parametrize("mode", MODES)
def test_ep_buffers_follow_the_call_rows(mode):
    mesh = make_test_mesh(1, 1)
    dense_p = M.moe_params_dense(jax.random.PRNGKey(0), CFG)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, CFG.d_model))
    mask = np.ones((B, T), np.float32)
    if mode == "decode":
        x, mask = x[:, :1], mask[:, :1]
        mask[1] = 0.0                       # a vacant slot
    elif mode == "dispatch":
        mask[2, 5:] = 0.0                   # chunk padding
    rows = x.shape[0] * x.shape[1]
    c2 = -(-rows // 8) * 8              # decode's 4 rows take a tile of 8
    ref, _ = M.moe_apply_dense(dense_p, CFG, x)
    results = []
    for scale in (1, 4):
        spec = _spec(mesh, scale)
        pl = M.uniform_placement(1, spec.slots, CFG.num_experts)
        (out, st), jaxpr = _apply(mode, spec, M.dense_to_ep(dense_p, pl),
                                  pl, x, mask)
        S, D = spec.slots, CFG.d_model
        # the slot buffer holds at most one pair of each row per slot
        assert _ffn_operands(jaxpr, S, D, []) == [(S, c2, D)] * 2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=5e-5)
        assert int(st["dropped"]) == 0
        assert int(st["rows_computed"]) == S * c2
        live = int(mask.sum()) if mode != "body3" else rows
        assert int(st["rows_filled"]) == live * CFG.top_k
        results.append((out, st))
    (o1, s1), (o4, s4) = results
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o4))
    for k in ("counts", "counts_per_rank", "local_frac", "dropped",
              "rows_filled", "rows_computed"):
        np.testing.assert_array_equal(np.asarray(s1[k]), np.asarray(s4[k]))


@pytest.mark.parametrize("cap,pairs,want", [
    (1288, 32, 32),             # decode: the batch, not the spec
    (1288, 5, 8),               # rounded up to the sublane tile
    (1288, 2576, 1288),         # never past the spec
    (6, 32, 6),
])
def test_row_bound(cap, pairs, want):
    assert M._row_bound(cap, pairs) == want
