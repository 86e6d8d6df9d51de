"""Shared benchmark setup: the paper-calibrated testbed and workloads.

Calibration note: absolute latencies depend on the paper's exact hardware
(A100 slices, Docker-tc 500 Mbps, MoE-Infinity runtime overheads). We
calibrate the linear time model so that baseline average latencies land in
the paper's reported range (units: seconds, Table II), and evaluate the
*orderings and relative gains*, which is what the paper's claims are about.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np

from repro.core.policies import ClusterView, get_policy
from repro.data.traces import (BIGBENCH_TASKS, MULTIDATA_TASKS,
                               poisson_workload)
from repro.serving.cluster import (ClusterSpec, DEEPSEEK_V2_LITE_PROFILE,
                                   MIXTRAL_PROFILE, ServerSpec)

# Edge-effective FLOP rates: single-request expert GEMV is HBM-bound, so the
# effective rate is far below peak (A100 ~ 2 TB/s => ~1 TFLOP/s effective
# at bf16 GEMV); server3 has 2 GPUs.
def calibrated_testbed(mem_fraction: float) -> ClusterSpec:
    return ClusterSpec(
        servers=(
            ServerSpec("server1", gpus=1, mem_bytes=mem_fraction * 40e9,
                       compute_speed=1.0e12, io_speed=4e9),
            ServerSpec("server2", gpus=1, mem_bytes=mem_fraction * 40e9,
                       compute_speed=1.0e12, io_speed=4e9),
            ServerSpec("server3", gpus=2, mem_bytes=mem_fraction * 2 * 40e9,
                       compute_speed=2.0e12, io_speed=8e9),
        ),
        bandwidth=500e6 / 8, rtt=30e-3)


MODELS = {
    "deepseek-v2-lite": (DEEPSEEK_V2_LITE_PROFILE, 0.3),
    "mixtral-8x7b": (MIXTRAL_PROFILE, 0.7),
}

WORKLOADS = {
    "bigbench": (list(BIGBENCH_TASKS), 10.0),    # 10 s Poisson arrivals
    "multidata": (list(MULTIDATA_TASKS), 20.0),  # 20 s Poisson arrivals
}


def make_setup(model: str, workload: str, *, duration: float = 1200.0,
               seed: int = 0):
    pf, frac = MODELS[model]
    cl = calibrated_testbed(frac)
    tasks, inter = WORKLOADS[workload]
    wl = poisson_workload(tasks, num_layers=pf.num_layers,
                          num_experts=pf.num_experts,
                          mean_interarrival=inter, duration=duration,
                          prompt_tokens=128, decode_tokens=20, seed=seed)
    cap = cl.expert_capacity(pf.expert_bytes)
    slots = np.minimum(np.maximum(cap // pf.num_layers, 1), pf.num_experts)
    return pf, cl, wl, cap, slots


# paper-name -> registered policy name (repro.core.policies registry)
POLICY_NAMES = {
    "Uniform": "uniform",
    "Redundance": "redundance",
    "SmartMoE": "smartmoe",
    "EPLB": "eplb",
    "DanceMoE": "dancemoe",
}


def all_plans(pf, cl, wl, cap, slots):
    freqs = wl.freqs_by_server(cl.n)
    cluster = ClusterView(capacity=cap, slots_cap=slots)
    return {label: get_policy(name).propose(freqs, cluster)
            for label, name in POLICY_NAMES.items()}


def run_cpu_leg(script: str, n_devices: int, timeout: float) -> str:
    """Run ``tests/md_scripts/<script>`` in a child process on
    ``n_devices`` placeholder host devices and return its output, which
    starts with the child's ``platform:`` line. The leg is CPU-only: the
    child is pinned to ``JAX_PLATFORMS=cpu`` (a parent that has touched JAX
    holds the chip, and the fake devices exist only on the CPU), so none of
    its numbers is a chip number."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tests", "md_scripts", script)
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, path], capture_output=True,
                          text=True, timeout=timeout, env=env)
    if proc.returncode != 0 or "ALL OK" not in proc.stdout:
        raise RuntimeError(
            f"{script} leg failed:\n{proc.stdout}\n{proc.stderr}")
    return proc.stdout
