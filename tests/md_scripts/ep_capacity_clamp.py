"""EP layer on 4 fake devices with its buffers clamped to the call's rows:
no pair drops and the output equals the dense oracle, in gather (decode)
and dispatch (chunk) mode, under uniform placement and with the hot expert
replicated on a second rank. Two experts take every row, so the clamped
bounds are reached exactly: a slot fills with one pair of every row."""
import jax
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_test_mesh
from repro.launch.serve import ep_capacities
from repro.models import moe as M

cfg = get_config("mixtral-8x7b").reduced()
E, K, D = cfg.num_experts, cfg.top_k, cfg.d_model
mesh = make_test_mesh(1, 4)
n_ep = 4
B, T = 8, 16                                    # 128 rows, 32 a rank
# a spec sized for steps four times larger: the clamp has to engage
C, C2 = ep_capacities(4 * B * T, K, n_ep)
spec = M.EPSpec.build(mesh, cfg, ep_axes=("model",), capacity=C,
                      slot_capacity=C2)
S = spec.slots
assert (E, n_ep, S) == (4, 4, 2), (E, n_ep, S)

# every row's top-2 is {0, 2}: a shared direction in the rows, which the
# router columns of experts 0 and 2 follow
u = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (D,)))
dense_p = M.moe_params_dense(jax.random.PRNGKey(0), cfg)
router = np.asarray(dense_p["router"]).copy()
router[:, 0] += 0.5 * u / np.linalg.norm(u)
router[:, 2] += 0.4 * u / np.linalg.norm(u)
dense_p = {**dense_p, "router": router}
x = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (B, T, D)) + u

uniform = M.uniform_placement(n_ep, S, E)
s2e = np.asarray(uniform.slot_to_expert).copy()
s2e[2, 1] = 0                                   # hot expert 0 on rank 2 too
replicated = M.placement_from_tables(s2e, num_experts=E)


def ffn_rows(jaxpr, out):
    """C2 of every grouped-FFN input dot ([S, C2, D] x [S, D, F])."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            if len(lhs) == 3 and lhs[0] == S and lhs[2] == D \
                    and rhs[:2] == (S, D):
                out.append(lhs[1])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    ffn_rows(inner, out)
    return out


with jax.set_mesh(mesh):
    # gather: one pair of each of the B rows; dispatch: one pair of each
    # of the 4 ranks' 32 rows
    for mode, xx, bound in (("decode", x[:, :1], B),
                            ("chunk", x, n_ep * (B * T // n_ep))):
        ref, ref_st = M.moe_apply_dense(dense_p, cfg, xx)
        n_rows = xx.shape[0] * xx.shape[1]
        assert np.asarray(ref_st["counts"]).tolist() == \
            [n_rows, 0, n_rows, 0], ref_st["counts"]
        for name, pl in (("uniform", uniform), ("replicated", replicated)):
            ep_p = M.dense_to_ep(dense_p, pl)

            def f(p, xi, q, m=mode):
                return M.moe_apply_ep(p, cfg, xi, mesh=mesh, spec=spec,
                                      placement=q, mode=m)
            out, st = jax.jit(f)(ep_p, xx, pl)
            rows = ffn_rows(jax.make_jaxpr(f)(ep_p, xx, pl).jaxpr, [])
            assert rows == [bound] * 2 and bound < C2, (mode, name, rows)
            assert int(st["dropped"]) == 0, (mode, name, st["dropped"])
            err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
            assert err < 5e-5, (mode, name, err)
            assert int(st["rows_filled"]) == n_rows * K, (mode, name)
            assert int(st["rows_computed"]) == n_ep * S * bound
            print(mode, name, "C2", rows[0], "of", C2, "err", err)
print("ALL OK")
