"""Config registry: every assigned architecture with its exact shape."""
import pytest

from repro.configs import get_config, list_configs
from repro.configs.base import INPUT_SHAPES

ASSIGNED = {
    "starcoder2-3b": dict(family="dense", num_layers=30, d_model=3072,
                          num_heads=24, num_kv_heads=2, d_ff=12288,
                          vocab_size=49152),
    "qwen2-vl-72b": dict(family="vlm", num_layers=80, d_model=8192,
                         num_heads=64, num_kv_heads=8, d_ff=29568,
                         vocab_size=152064),
    "tinyllama-1.1b": dict(family="dense", num_layers=22, d_model=2048,
                           num_heads=32, num_kv_heads=4, d_ff=5632,
                           vocab_size=32000),
    "falcon-mamba-7b": dict(family="ssm", num_layers=64, d_model=4096,
                            d_ff=0, vocab_size=65024, ssm_state=16),
    "zamba2-2.7b": dict(family="hybrid", num_layers=54, d_model=2560,
                        num_heads=32, num_kv_heads=32, d_ff=10240,
                        vocab_size=32000, ssm_state=64),
    "musicgen-large": dict(family="audio", num_layers=48, d_model=2048,
                           num_heads=32, num_kv_heads=32, d_ff=8192,
                           vocab_size=2048),
    "command-r-plus-104b": dict(family="dense", num_layers=64, d_model=12288,
                                num_heads=96, num_kv_heads=8, d_ff=33792,
                                vocab_size=256000),
    "llama4-maverick-400b-a17b": dict(family="moe", num_layers=48,
                                      d_model=5120, num_heads=40,
                                      num_kv_heads=8, d_ff=8192,
                                      vocab_size=202048, num_experts=128,
                                      top_k=1),
    "yi-6b": dict(family="dense", num_layers=32, d_model=4096, num_heads=32,
                  num_kv_heads=4, d_ff=11008, vocab_size=64000),
    "phi3.5-moe-42b-a6.6b": dict(family="moe", num_layers=32, d_model=4096,
                                 num_heads=32, num_kv_heads=8, d_ff=6400,
                                 vocab_size=32064, num_experts=16, top_k=2),
}

PARAM_RANGES = {  # billions: generous envelopes around the advertised sizes
    "starcoder2-3b": (2.5, 5.0), "qwen2-vl-72b": (65, 80),
    "tinyllama-1.1b": (0.9, 1.3), "falcon-mamba-7b": (6.5, 8.5),
    "zamba2-2.7b": (1.8, 3.2), "musicgen-large": (2.7, 3.8),
    "command-r-plus-104b": (95, 112),
    "llama4-maverick-400b-a17b": (360, 430), "yi-6b": (5.4, 6.8),
    "phi3.5-moe-42b-a6.6b": (38, 46),
}


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_assigned_config_exact(name):
    cfg = get_config(name)
    for key, val in ASSIGNED[name].items():
        assert getattr(cfg, key) == val, (name, key)
    assert cfg.source  # every config cites its source


@pytest.mark.parametrize("name", sorted(PARAM_RANGES))
def test_param_counts(name):
    lo, hi = PARAM_RANGES[name]
    p = get_config(name).param_count() / 1e9
    assert lo <= p <= hi, f"{name}: {p:.2f}B not in [{lo}, {hi}]"


def test_papers_models_registered():
    assert "mixtral-8x7b" in list_configs()
    assert "deepseek-v2-lite" in list_configs()
    dsl = get_config("deepseek-v2-lite")
    assert dsl.num_experts == 64 and dsl.top_k == 8


def test_input_shapes():
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["long_500k"].seq_len == 524288
    assert INPUT_SHAPES["decode_32k"].kind == "decode"


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_reduced_variants(name):
    r = get_config(name).reduced()
    assert r.d_model <= 512 and r.num_experts <= 4
    pat, groups = r.layer_pattern()
    assert groups * len([k for k in pat]) >= 1
    # reduced keeps the family and pattern structure
    assert r.family == get_config(name).family
    assert pat == get_config(name).layer_pattern()[0]


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_head_padding_function_preserving(name):
    cfg = get_config(name)
    if not cfg.num_heads:
        return
    hp = cfg.padded_heads(16)
    assert hp % 16 == 0 and hp >= cfg.num_heads
    assert hp % cfg.num_kv_heads == 0


def test_peak_table_is_keyed_by_device_kind():
    from repro.launch.mesh import peaks

    v5e = peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("cpu")
