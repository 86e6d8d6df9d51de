"""The Pallas kernels compile for a described TPU v5e at real widths.

Nothing runs: each test lowers and compiles one kernel for a chip of a
``v5e:2x2`` topology that is described, not attached, and checks that the
Mosaic kernel is in the compiled program. This catches what interpret mode
cannot, such as a block that does not fit the scoped VMEM or an operation
Mosaic does not lower. The topology is described inside a fixture only, so
that one pytest worker loads the TPU compiler and the others collect the
same tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention, moe_gmm, ssm_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def shape(one_chip, no_persistent_cache):
    def make(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "arch", ["mixtral-8x7b", "deepseek-v2-lite", "phi3.5-moe-42b-a6.6b"]
)
def test_moe_gmm_compiles_at_published_width(arch, shape):
    cfg = get_config(arch)
    S, C, D, F = cfg.num_experts, 128, cfg.d_model, cfg.d_ff
    _assert_kernel(
        lambda x, w1, w3, w2: moe_gmm.moe_gmm(x, w1, w3, w2, interpret=False),
        shape((S, C, D)),
        shape((S, D, F)),
        shape((S, D, F)),
        shape((S, F, D)),
    )


def test_flash_attention_compiles_at_hd128_4k(shape):
    qkv = shape((32, 4096, 128))
    _assert_kernel(
        lambda q, k, v: flash_attention.flash_attention(q, k, v, interpret=False),
        qkv,
        qkv,
        qkv,
    )


def test_ssm_scan_compiles_at_falcon_mamba_width(shape):
    cfg = get_config("falcon-mamba-7b")
    B, T, d, N = 1, 256, cfg.d_inner, cfg.ssm_state
    _assert_kernel(
        lambda *a: ssm_scan.ssm_scan(*a, interpret=False),
        shape((B, T, d)),
        shape((B, T, d)),
        shape((B, T, N)),
        shape((B, T, N)),
        shape((d, N), jnp.float32),
        shape((d,), jnp.float32),
    )
