"""EP MoE layer == dense oracle, on a 2x4 fake mesh, all modes/EP layouts."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import moe as M

cfg = get_config("mixtral-8x7b").reduced()
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh(2, 4)
key = jax.random.PRNGKey(0)
dense_p = M.moe_params_dense(key, cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model))
ref, ref_stats = M.moe_apply_dense(dense_p, cfg, x)

for ep_axes in [("model",), ("data", "model")]:
    n_ep = int(np.prod([mesh.shape[a] for a in ep_axes]))
    spec = M.EPSpec.build(mesh, cfg, ep_axes=ep_axes,
                          slots=max(2, -(-cfg.num_experts // n_ep) + 1),
                          capacity=8 * 16 * 2, slot_capacity=8 * 16 * 2 * n_ep)
    for pl_name, pl in [
        ("uniform", M.uniform_placement(n_ep, spec.slots, cfg.num_experts)),
    ]:
        ep_p = M.dense_to_ep(dense_p, pl)
        with jax.set_mesh(mesh):
            for mode in ["prefill", "decode"]:
                xx = x if mode != "decode" else x[:, :1]
                rr = ref if mode != "decode" else \
                    M.moe_apply_dense(dense_p, cfg, xx)[0]
                out, stats = jax.jit(
                    lambda p, xi, q, m=mode: M.moe_apply_ep(
                        p, cfg, xi, mesh=mesh, spec=spec, placement=q,
                        mode=m))(ep_p, xx, pl)
                err = float(jnp.max(jnp.abs(out - rr)))
                assert err < 5e-5, (ep_axes, pl_name, mode, err)
                c = float(stats["counts"].sum())
                expect = xx.shape[0] * xx.shape[1] * cfg.top_k
                assert abs(c - expect) < 1e-3, (mode, c, expect)
                lf = float(stats["local_frac"])
                assert 0.0 <= lf <= 1.0
print("ALL OK")
