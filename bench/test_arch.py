"""A configuration brings its own model files: the harness builds, checks
and counts an architecture it has never seen from the files the
configuration names, and the Mixtral module reads as the code it was
moved out of did."""

import hashlib
import json
import textwrap
import time

import numpy as np
import pytest

from bench import harness, tiny
from bench import weights as W

SEED = 2**33 + 11
MIXTRAL = harness.load_module("bench/arch/mixtral.py")
REFERENCE = harness.load_module("bench/arch/mixtral_reference.py")

# an architecture the harness has not seen: the Mixtral block under other
# configuration keys, with its leaves drawn in another order, its own work
# count and its own reference (a wrapper that notes each call)
TOY_ARCH = '''
from bench import harness

base = harness.load_module("bench/arch/mixtral.py")
CALLS = []
TINY = {}


class Dims(base.Dims):
    LEAVES = dict(reversed(list(base.Dims.LEAVES.items())))


def dims(c):
    return Dims(d=c["n_embd"], heads=c["n_head"], kv_heads=c["n_kv"],
                hd=c["n_embd"] // c["n_head"], f=c["d_expert"],
                experts=c["n_expert"], top_k=c["k"], vocab=c["n_vocab"],
                layers=c["n_layer"], rope_theta=1e6, eps=1e-5)


def model_config(conf, name):
    from repro.configs.base import ModelConfig

    d = dims(conf)
    return ModelConfig(name=name, family="moe", num_layers=d.layers,
                       d_model=d.d, num_heads=d.heads,
                       num_kv_heads=d.kv_heads, d_ff=d.f,
                       vocab_size=d.vocab, head_dim=d.hd,
                       num_experts=d.experts, top_k=d.top_k, moe_every=1,
                       rope_theta=d.rope_theta, norm_eps=d.eps, source="toy")


params = base.params


def work(c, kind, call, block_size):
    CALLS.append(kind)
    return {"step": {"flops": 1.0, "bytes": 1.0}}
'''
TOY_REFERENCE = '''
from bench import harness

base = harness.load_module("bench/arch/mixtral_reference.py")
CALLS = []


def forward_logits(dims, seed, seqs, length, rows, control=False):
    CALLS.append(type(dims).__name__)
    return base.forward_logits(dims, seed, seqs, length, rows,
                               control=control)
'''


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    d = tmp_path_factory.mktemp("toy")
    (d / "toy_arch.py").write_text(textwrap.dedent(TOY_ARCH))
    (d / "toy_reference.py").write_text(textwrap.dedent(TOY_REFERENCE))
    bm, cell, _, traffic = tiny.cell(1)
    conf = {"n_embd": 128, "n_head": 4, "n_kv": 2, "d_expert": 256,
            "n_expert": 8, "k": 2, "n_vocab": 32000, "n_layer": 2,
            "source": "toy", "arch": str(d / "toy_arch.py"),
            "reference": str(d / "toy_reference.py"),
            "limits": {"mean_gap": 0.15}}
    r = harness.run("tiny", SEED, 2.0, True, t_start=time.perf_counter(),
                    chips_check=False, cell_override=(bm, cell, conf, traffic),
                    log=lambda *a, **k: None)
    return r, harness.load_module(conf["arch"]), \
        harness.load_module(conf["reference"])


def test_a_configuration_brings_its_own_model(toy):
    r, arch, ref = toy
    assert r["correct"] is True
    assert ref.CALLS == ["Dims"]
    assert "decode" in arch.CALLS and "chunk" in arch.CALLS
    assert arch.Dims is not MIXTRAL.Dims
    # the program's own counters reach the readers
    assert r["metrics"]["prefill_tokens_per_call"]["value"] > 0


def test_another_leaf_order_is_another_model():
    """The toy's reordered leaf table draws other weights from one seed:
    a seed's meaning is its architecture's table."""
    key = W.base_key(SEED)
    conf = dict(json.loads((harness.ROOT / "bench" / "configs"
                            / "mixtral-8x7b-2l.json").read_text()),
                **MIXTRAL.TINY)
    d = MIXTRAL.dims(conf)

    class Reordered(MIXTRAL.Dims):
        LEAVES = dict(reversed(list(MIXTRAL.Dims.LEAVES.items())))

    r = Reordered(**{f: getattr(d, f) for f in d.__dataclass_fields__})
    assert not np.array_equal(np.asarray(W.leaf(key, d, "wq", 0)),
                              np.asarray(W.leaf(key, r, "wq", 0)))


# the Mixtral module against the code it was moved out of, frozen from
# that code at a small size: its weights bit for bit, the reference's
# logits, and the needed work of a decode and a chunk call
SMALL = MIXTRAL.Dims(d=64, heads=4, kv_heads=2, hd=16, f=128, experts=8,
                     top_k=2, vocab=256, layers=2, rope_theta=1e6, eps=1e-5)
FULL = MIXTRAL.Dims(d=4096, heads=32, kv_heads=8, hd=128, f=14336,
                    experts=8, top_k=2, vocab=32000, layers=2,
                    rope_theta=1e6, eps=1e-5)
WEIGHTS_SHA256 = \
    "2c0da26a59fbe8a7f0fdfa210e8ef62b813d946b4bdc5e0349a47776a7282632"


def test_mixtral_weights_are_the_seeds_they_were():
    key = W.base_key(SEED)
    h = hashlib.sha256()
    for name in MIXTRAL.Dims.LEAVES:
        layers = (0,) if name in ("embed", "lm_head", "final_norm") else (0, 1)
        for layer in layers:
            h.update(np.asarray(W.leaf(key, SMALL, name, layer)).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


def test_mixtral_reference_reads_as_it_did():
    from bench import compare

    seqs = [(np.array([5, 9, 200, 3, 77], np.int32),
             np.array([1, 2, 3, 4], np.int32)),
            (np.array([250, 0, 11], np.int32),
             np.array([9, 8, 7, 6, 5, 4], np.int32))]
    ref, ctl, margin = REFERENCE.forward_logits(SMALL, SEED, seqs, 16, 12,
                                                control=True)
    assert ref.shape == (10, 256)
    assert float(ref.astype(np.float64).sum()) == pytest.approx(
        -23.069693481578724, rel=1e-5)
    assert float(np.abs(ref).astype(np.float64).sum()) == pytest.approx(
        318.45916991107515, rel=1e-6)
    assert ref[0, :4] == pytest.approx(
        [-0.19703523814678192, 0.21925732493400574, -0.015481159090995789,
         0.1391599327325821], rel=1e-5)
    assert float(ctl.astype(np.float64).sum()) == pytest.approx(
        -21.612353088421514, rel=1e-4)
    assert margin[:3] == pytest.approx(
        [0.03109198808670044, 0.014254510402679443, 0.0091780424118042],
        rel=1e-4)
    g = compare.gaps(ref, np.concatenate([s for _, s in seqs]))
    assert g[:4] == pytest.approx([1.2168571949005127, 2.386897325515747,
                                   1.7636253833770752, 3.293172597885132],
                                  rel=1e-5)


def test_mixtral_work_counts_as_it_did():
    counts = np.zeros((2, 8))
    counts[0, :2] = counts[1, :3] = 1
    dec = MIXTRAL.work(FULL, "decode", {
        "pos": np.array([99, 6, 299, 0]), "mask": np.array([1.0, 1, 1, 0]),
        "counts": counts}, 16)["step"]
    assert dec == {"flops": 5531336704.0, "bytes": 2195054592.0, "tokens": 3}
    counts[0, :4] = 1
    counts[1, :5] = 1
    mask = np.zeros((3, 16))
    mask[0, :16] = mask[1, :9] = 1
    chunk = MIXTRAL.work(FULL, "chunk", {
        "offsets": np.array([0, 32, 0]), "mask": mask, "counts": counts},
        16)["step"]
    assert chunk == {"flops": 39707246592.0, "bytes": 3601653760.0,
                     "tokens": 25}
