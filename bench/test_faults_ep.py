"""The four-chip fault: the EP layer's exchange between chips left out,
on four CPU devices in a child process, comes out not correct while the
same run without the fault is correct."""

import json
import os
import subprocess
import sys

from bench.harness import ROOT


def test_exchange_left_out_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-m", "bench.faults", "exchange", "4"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True
    assert out["exchange"]["correct"] is False
