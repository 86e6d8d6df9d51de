"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned family runs one train step + prefill + decode on CPU, asserting
output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs import ASSIGNED_ARCHS
from repro.models import transformer as tr
from repro.optim.adamw import adamw
from repro.training.train_loop import make_train_step

B, T = 2, 16


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


def _runtime(name):
    cfg = get_config(name).reduced()
    return tr.Runtime(cfg=cfg), cfg


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_train_step(name, rng):
    rt, cfg = _runtime(name)
    params = tr.init_params(rt, rng)
    toks = jax.random.randint(rng, (B, T), 0, cfg.vocab_size)
    step = make_train_step(rt, adamw(lr=1e-3))
    opt_state = adamw(lr=1e-3).init(params)
    params2, _, metrics = jax.jit(step)(params, opt_state, toks,
                                        jnp.roll(toks, -1, 1))
    assert jnp.isfinite(metrics["loss"]), name
    # params actually changed
    delta = sum(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert delta > 0, name


@pytest.mark.parametrize("name", ASSIGNED_ARCHS)
def test_prefill_decode(name, rng):
    rt, cfg = _runtime(name)
    params = tr.init_params(rt, rng)
    if cfg.frontend != "none":
        # modality stub: the backbone consumes precomputed embeddings
        embeds = jax.random.normal(rng, (B, T, cfg.d_model)) * 0.02
        logits, cache, _ = tr.prefill(rt, params, embeds=embeds,
                                      cache_len=T + 4)
    else:
        toks = jax.random.randint(rng, (B, T), 0, cfg.vocab_size)
        logits, cache, _ = tr.prefill(rt, params, tokens=toks,
                                      cache_len=T + 4)
    assert logits.shape == (B, cfg.vocab_size), name
    assert not bool(jnp.isnan(logits).any()), name
    nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache2, _ = tr.decode_step(rt, params, cache, nxt, jnp.int32(T))
    assert logits2.shape == (B, cfg.vocab_size), name
    assert not bool(jnp.isnan(logits2).any()), name
    # cache structure preserved
    assert jax.tree.structure(cache) == jax.tree.structure(cache2)


@pytest.mark.parametrize("name", ["tinyllama-1.1b", "zamba2-2.7b",
                                  "falcon-mamba-7b", "phi3.5-moe-42b-a6.6b"])
def test_decode_matches_prefill(name, rng):
    """Incremental decode == one-shot forward at the last position."""
    rt, cfg = _runtime(name)
    params = tr.init_params(rt, rng)
    toks = jax.random.randint(rng, (B, T + 1), 0, cfg.vocab_size)
    full, _, _ = tr.prefill(rt, params, tokens=toks)
    part, cache, _ = tr.prefill(rt, params, tokens=toks[:, :T],
                                cache_len=T + 4)
    inc, _, _ = tr.decode_step(rt, params, cache, toks[:, T:T + 1],
                               jnp.int32(T))
    assert float(jnp.max(jnp.abs(full - inc))) < 5e-4, name
