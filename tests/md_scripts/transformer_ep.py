"""Full transformer with EP MoE == dense impl, on a 2x4 fake mesh:
train loss (ce), prefill logits, decode logits; plus a migration swap."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.placement import build_ep_placement, dancemoe_placement
from repro.models import moe as M
from repro.models import transformer as tr

cfg = get_config("mixtral-8x7b").reduced()
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh(2, 4)
spec = M.EPSpec.build(mesh, cfg, ep_axes=("model",), slots=2,
                      capacity=512, slot_capacity=2048)
pl = M.uniform_placement(spec.n_ep, spec.slots, cfg.num_experts)
_, n_groups = cfg.layer_pattern()
pls = tr.stack_placement(pl, n_groups)
key = jax.random.PRNGKey(0)
rt_d = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="dense")
rt_e = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="ep", ep_spec=spec)
params_d = tr.init_params(rt_d, key)
gd = params_d["groups"]
ge = dict(gd)
for k, v in gd.items():
    if "router" in v:
        per = [M.dense_to_ep(jax.tree.map(lambda a: a[g], v), pl)
               for g in range(n_groups)]
        ge[k] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
params_e = dict(params_d)
params_e["groups"] = ge
B, T = 4, 16
toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)

with jax.set_mesh(mesh):
    (_, md) = jax.jit(lambda p, t: tr.loss_fn(rt_d, p, t,
                                              jnp.roll(t, -1, 1)))(params_d,
                                                                   toks)
    (_, me) = jax.jit(lambda p, t, q: tr.loss_fn(
        rt_e, p, t, jnp.roll(t, -1, 1), placement=q))(params_e, toks, pls)
    assert abs(float(md["ce_loss"]) - float(me["ce_loss"])) < 1e-3
    lg_d, cd, _ = jax.jit(lambda p, t: tr.prefill(rt_d, p, tokens=t))(
        params_d, toks)
    lg_e, ce, _ = jax.jit(lambda p, t, q: tr.prefill(
        rt_e, p, tokens=t, placement=q))(params_e, toks, pls)
    assert float(jnp.max(jnp.abs(lg_d - lg_e))) < 5e-5
    d_d, _, _ = jax.jit(lambda p, c, t: tr.decode_step(
        rt_d, p, c, t, jnp.int32(T)))(params_d, cd, toks[:, :1])
    d_e, _, _ = jax.jit(lambda p, c, t, q: tr.decode_step(
        rt_e, p, c, t, jnp.int32(T), placement=q))(params_e, ce,
                                                   toks[:, :1], pls)
    assert float(jnp.max(jnp.abs(d_d - d_e))) < 5e-5

    # migration: a DanceMoE placement (with replication) must compute the
    # SAME function once weights are re-gathered (zero-recompile swap)
    freqs = np.random.default_rng(0).dirichlet(
        np.full(cfg.num_experts, 0.5), size=(n_groups, spec.n_ep))
    plan = dancemoe_placement(freqs, np.full(spec.n_ep, spec.slots * n_groups),
                              np.full(spec.n_ep, spec.slots))
    pls2 = build_ep_placement(plan, spec.slots)
    ge2 = dict(gd)
    for k, v in gd.items():
        if "router" in v:
            per = [M.dense_to_ep(jax.tree.map(lambda a: a[g], v),
                                 jax.tree.map(lambda a: a[g], pls2))
                   for g in range(n_groups)]
            ge2[k] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    params_e2 = dict(params_d)
    params_e2["groups"] = ge2
    lg_m, _, sm = jax.jit(lambda p, t, q: tr.prefill(
        rt_e, p, tokens=t, placement=q))(params_e2, toks, pls2)
    assert float(jnp.max(jnp.abs(lg_d - lg_m))) < 5e-5
print("ALL OK")
