"""The chip a run is measured on: the device check, the published peaks
and the persistent compilation cache.

Kept apart from the program so that the yardstick cannot move with it.
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; exits non-zero, printing nothing on
    standard output, when JAX finds no TPU or fewer than ``n`` chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX sees {len(devices)}")
    peaks(devices[0].device_kind)
    return devices[:n]


def use_compile_cache() -> Path:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (the path is part of the cache key), every program kept."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR
