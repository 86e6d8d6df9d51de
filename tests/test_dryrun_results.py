"""Validate the dry-run deliverable: every (arch x shape x mesh) combination
compiled, and the roofline records are complete and sane. Skips when the
sweep has not been run (results/ is generated, not committed state)."""
import glob
import json
from pathlib import Path

import pytest

from repro.configs import ASSIGNED_ARCHS

RESULTS = Path(__file__).parent.parent / "results" / "dryrun"
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
MESHES = ["16x16", "2x16x16"]

have = sorted(glob.glob(str(RESULTS / "*.json")))
pytestmark = pytest.mark.skipif(
    len(have) < 10, reason="dry-run sweep not run (python -m "
    "repro.launch.dryrun --all --both-meshes)")


@pytest.mark.parametrize("mesh", MESHES)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_pair_compiled(arch, shape, mesh):
    f = RESULTS / f"{arch}__{shape}__{mesh}.json"
    if not f.exists():
        pytest.skip(f"{f.name} not generated yet")
    r = json.loads(f.read_text())
    assert r.get("ok"), r.get("error")
    assert r["chips"] == (512 if mesh == "2x16x16" else 256)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_roofline_terms_sane(arch):
    for shape in SHAPES:
        f = RESULTS / f"{arch}__{shape}__16x16.json"
        if not f.exists():
            continue
        r = json.loads(f.read_text())
        if not r.get("ok"):
            continue
        ro = r["roofline"]
        assert ro["compute_s"] >= 0 and ro["memory_s"] > 0
        assert ro["dominant"] in ("compute", "memory", "collective")
        assert 0 < ro["useful_flops_ratio"] < 20
        # decode shapes must not be compute-dominated on this hardware
        if shape in ("decode_32k", "long_500k"):
            assert ro["dominant"] != "compute", (arch, shape)
