"""The system-level claim on-device: a DanceMoE activation-aware placement
achieves a higher local compute ratio than Uniform on skewed traffic (the
JAX analogue of the paper's Fig. 6)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.placement import build_ep_placement, dancemoe_placement
from repro.models import moe as M
from repro.models import transformer as tr

cfg = get_config("mixtral-8x7b").reduced()   # 4 experts, top-2
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh(2, 4)
spec = M.EPSpec.build(mesh, cfg, ep_axes=("model",), slots=2,
                      capacity=512, slot_capacity=2048)
_, n_groups = cfg.layer_pattern()
rt_d = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="dense")
rt = tr.Runtime(cfg=cfg, mesh=mesh, moe_impl="ep", ep_spec=spec)
key = jax.random.PRNGKey(0)
params_dense = tr.init_params(rt_d, key)


def regather(pls):
    groups = dict(params_dense["groups"])
    for k, v in params_dense["groups"].items():
        if "router" in v:
            per = [M.dense_to_ep(jax.tree.map(lambda a: a[g], v),
                                 jax.tree.map(lambda a: a[g], pls))
                   for g in range(n_groups)]
            groups[k] = jax.tree.map(lambda *xs: jnp.stack(xs), *per)
    out = dict(params_dense)
    out["groups"] = groups
    return out


B, T = 8, 32
toks = jax.random.randint(key, (B, T), 0, cfg.vocab_size)

pl_u = M.uniform_placement(spec.n_ep, spec.slots, cfg.num_experts)
pls_u = tr.stack_placement(pl_u, n_groups)
with jax.set_mesh(mesh):
    _, _, st = jax.jit(lambda p, t, q: tr.prefill(
        rt, p, tokens=t, placement=q))(regather(pls_u), toks, pls_u)
counts = np.asarray(st["counts_per_rank"], np.float64)   # [G, n_ep, E]
lf_uniform = float(st["local_frac"].mean())

freqs = counts / np.maximum(counts.sum(-1, keepdims=True), 1e-9)
plan = dancemoe_placement(freqs, np.full(spec.n_ep, spec.slots * n_groups),
                          np.full(spec.n_ep, spec.slots))
pls_d = build_ep_placement(plan, spec.slots)
with jax.set_mesh(mesh):
    lg_d, _, st2 = jax.jit(lambda p, t, q: tr.prefill(
        rt, p, tokens=t, placement=q))(regather(pls_d), toks, pls_d)
lf_dance = float(st2["local_frac"].mean())
assert lf_dance > lf_uniform, (lf_dance, lf_uniform)
print(f"local ratio uniform={lf_uniform:.3f} dancemoe={lf_dance:.3f}")
print("ALL OK")
