"""The needed-work counts behind every roofline and MFU (the Mixtral
architecture module's), against hand counts at the published widths."""

import inspect

import numpy as np
import pytest

from bench import flops
from bench import harness

F = harness.load_module("bench/arch/mixtral.py")
Dims = F.Dims
MIXTRAL = Dims(d=4096, heads=32, kv_heads=8, hd=128, f=14336, experts=8,
               top_k=2, vocab=32000, layers=2, rope_theta=1e6, eps=1e-5)
PHI = Dims(d=4096, heads=32, kv_heads=8, hd=128, f=6400, experts=16,
           top_k=2, vocab=32064, layers=4, rope_theta=1e4, eps=1e-5)


def test_decode_step_by_hand_mixtral():
    w = F.decode_step(MIXTRAL, np.array([100]), np.array([2, 2]))
    attn = 4096 * (4096 + 2 * 1024) + 4096 * 4096         # q, k, v, o
    per_token = 2 * attn + 2 * 4096 * 8 + 2 * 2 * 3 * 4096 * 14336
    layer = per_token + 4 * 32 * 128 * 100                 # scores + values
    assert w["flops"] == 2 * layer + 2 * 4096 * 32000
    params = (2 * (attn + 4096 * 8 + 2 * 4096)             # 2 layers
              + 4 * 3 * 4096 * 14336                       # 4 experts touched
              + 4096 * 32000 + 4096 + 4096)                # head, norm, 1 row
    kv = (99 + 1) * 2 * 8 * 128 * 2 * 2                    # read 99, write 1
    assert w["bytes"] == 2 * params + kv
    assert w["tokens"] == 1


def test_prefill_chunk_by_hand_phi():
    w = F.prefill_chunk(PHI, np.array([32]), np.array([16]), np.array([4] * 4),
                        block_size=16)
    attn = 4096 * 6144 + 4096 * 4096
    per_token = 2 * attn + 2 * 4096 * 16 + 2 * 2 * 3 * 4096 * 6400
    keys = sum(range(33, 49))                              # positions 32..47
    layer = 16 * per_token + 4 * 32 * 128 * keys
    assert w["flops"] == 4 * layer                         # a full block: no logits
    params = (4 * (attn + 4096 * 16 + 2 * 4096) + 16 * 3 * 4096 * 6400
              + 4096 * 32064 + 4096 + 16 * 4096)
    kv = (32 + 16) * 2 * 8 * 128 * 2 * 4
    assert w["bytes"] == 2 * params + kv


def test_short_chunk_counts_its_logits():
    full = F.prefill_chunk(PHI, np.array([0]), np.array([16]), np.ones(4), 16)
    short = F.prefill_chunk(PHI, np.array([0]), np.array([15]), np.ones(4), 16)
    one_token = F.token_flops(PHI, np.array([16]))
    assert full["flops"] - short["flops"] == pytest.approx(
        one_token - 2 * 4096 * 32064)


def test_padding_never_counts():
    """Rows the step carries but does not serve (mask 0) add nothing, and
    the counts take no capacity, page-table width or padded batch."""
    counts = np.zeros((2, 8))
    counts[:, :3] = 1
    live = {"on": True,
            "decode": [{"pos": np.array([5, 9]), "mask": np.array([1.0, 1.0]),
                        "counts": counts}],
            "chunk": [{"offsets": np.array([0]), "mask": np.ones((1, 16)),
                       "counts": counts}]}
    padded = {"on": True,
              "decode": [{"pos": np.array([5, 9, 0, 0]),
                          "mask": np.array([1.0, 1.0, 0, 0]),
                          "counts": counts}],
              "chunk": [{"offsets": np.array([0, 0]),
                         "mask": np.concatenate([np.ones((1, 16)),
                                                 np.zeros((1, 16))]),
                         "counts": counts}]}
    a = harness._work(live, F, MIXTRAL, 16)
    b = harness._work(padded, F, MIXTRAL, 16)
    assert a == b
    assert set(a["decode"][0]) == {"step", "attn", "moe.expert_ffn"}
    for mod in (F, flops):
        src = inspect.getsource(mod)
        for word in ("C2", "capacity", "max_pages", "slot"):
            assert word not in src


@pytest.mark.parametrize("dims", [MIXTRAL, PHI], ids=["mixtral", "phi"])
def test_roofline_bound_and_share(dims):
    w = F.decode_step(dims, np.full(32, 300), np.full(dims.layers, dims.experts))
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least = max(w["flops"] / 197e12, w["bytes"] / 819e9)
    r = flops.roofline(w, 2 * least, peaks, chips=1)
    assert r["share"] == pytest.approx(0.5)
    assert r["bound"] == "hbm"          # 32 decode rows read every expert


def test_scope_work_by_hand():
    """The per-scope counts behind the kernels' rooflines: attention and
    the expert FFN of one decode row, each within the whole step's."""
    counts = np.zeros((2, 8))
    counts[:, :2] = 1
    w = F.work(MIXTRAL, "decode", {"pos": np.array([99, 0]),
                                   "mask": np.array([1.0, 0.0]),
                                   "counts": counts}, 16)
    attn = 4096 * (4096 + 2 * 1024) + 4096 * 4096
    assert w["attn"]["flops"] == 2 * (2 * attn + 4 * 32 * 128 * 100)
    assert w["attn"]["bytes"] == 2 * 2 * attn + (99 + 1) * 2 * 8 * 128 * 2 * 2
    expert = 3 * 4096 * 14336
    assert w["moe.expert_ffn"]["flops"] == 2 * 2 * 2 * expert
    assert w["moe.expert_ffn"]["bytes"] == 2 * (4 * expert + 2 * 2 * 2 * 4096)
    for k in ("flops", "bytes"):
        assert w["attn"][k] + w["moe.expert_ffn"][k] <= w["step"][k]
