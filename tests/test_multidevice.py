"""SPMD correctness on 8 fake CPU devices — run in subprocesses so the fake
device count never leaks into the rest of the suite (per the assignment,
XLA_FLAGS must not be set globally)."""
import os
import subprocess
import sys
from pathlib import Path


SCRIPTS = Path(__file__).parent / "md_scripts"


def run_script(name: str, timeout: int = 560, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(Path(__file__).parent.parent / "src")
    r = subprocess.run([sys.executable, str(SCRIPTS / name)], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"{name} failed:\n{r.stdout}\n{r.stderr}"
    return r.stdout


def test_ep_moe_matches_dense_oracle():
    out = run_script("ep_moe_equivalence.py")
    assert "ALL OK" in out


def test_transformer_ep_end_to_end():
    out = run_script("transformer_ep.py")
    assert "ALL OK" in out


def test_placement_quality_affects_local_ratio():
    out = run_script("placement_local_ratio.py")
    assert "ALL OK" in out


def test_layout_equivalence():
    out = run_script("layout_equivalence.py")
    assert "ALL OK" in out


def test_ep_capacity_clamp_is_dropless_on_four_devices():
    out = run_script("ep_capacity_clamp.py", devices=4)
    assert "ALL OK" in out
