"""Sharding layouts (tp / sp / cp / fsdp) must compute the SAME function:
loss and prefill logits agree across layouts on a 2x4 fake mesh."""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import moe as M
from repro.models import transformer as tr

from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh(2, 4)
key = jax.random.PRNGKey(0)

# dense arch across all layouts
cfg = get_config("tinyllama-1.1b").reduced()
rt0 = tr.Runtime(cfg=cfg, mesh=mesh, layout="tp")
params = tr.init_params(rt0, key)
B, T = 4, 32
toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)
ref_loss = ref_lg = None
for layout in ("tp", "sp", "cp", "fsdp"):
    rt = tr.Runtime(cfg=cfg, mesh=mesh, layout=layout,
                    remat_policy="dots+kv" if layout != "tp" else "none")
    with jax.set_mesh(mesh):
        loss, _ = jax.jit(lambda p, t: tr.loss_fn(rt, p, t,
                                                  jnp.roll(t, -1, 1)))(params, toks)
        lg, _, _ = jax.jit(lambda p, t: tr.prefill(rt, p, tokens=t))(params, toks)
    if ref_loss is None:
        ref_loss, ref_lg = float(loss), lg
    else:
        assert abs(float(loss) - ref_loss) < 2e-3, (layout, float(loss), ref_loss)
        err = float(jnp.max(jnp.abs(lg - ref_lg)))
        assert err < 5e-4, (layout, err)
    print(f"dense {layout}: loss={float(loss):.4f} OK")

# MoE arch: tp vs sp/fsdp EP row path
cfg = get_config("mixtral-8x7b").reduced()
spec = M.EPSpec.build(mesh, cfg, ep_axes=("model",), slots=2,
                      capacity=512, slot_capacity=2048)
pl = M.uniform_placement(spec.n_ep, spec.slots, cfg.num_experts)
_, n_groups = cfg.layer_pattern()
pls = tr.stack_placement(pl, n_groups)
rt_d = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="dense")
params_d = tr.init_params(rt_d, key)
ge = dict(params_d["groups"])
for k, v in params_d["groups"].items():
    if "router" in v:
        per = [M.dense_to_ep(jax.tree.map(lambda a: a[g], v), pl)
               for g in range(n_groups)]
        ge[k] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
params_e = dict(params_d)
params_e["groups"] = ge
with jax.set_mesh(mesh):
    lg_ref, _, _ = jax.jit(lambda p, t: tr.prefill(rt_d, p, tokens=t))(params_d, toks)
    for layout in ("tp", "sp", "fsdp"):
        rt = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="ep", ep_spec=spec,
                        layout=layout)
        lg, _, st = jax.jit(lambda p, t, q: tr.prefill(
            rt, p, tokens=t, placement=q))(params_e, toks, pls)
        err = float(jnp.max(jnp.abs(lg - lg_ref)))
        assert err < 5e-4, (layout, err)
        print(f"moe {layout}: prefill err={err:.2e} OK")
print("ALL OK")
