"""Production mesh construction and the per-chip peak table.

Target: TPU v5e, 256 chips/pod. Single-pod mesh (16, 16) = ("data",
"model"); multi-pod (2, 16, 16) = ("pod", "data", "model"). Functions, not
module constants — importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) devices exist."""
    return _make_mesh((data, model), ("data", "model"))


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
# HBM, 1,600 Gbit/s inter-chip interconnect per chip over its 4 ICI links.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_link_bytes_per_s": 1600e9 / 8 / 4,
    },
}
TARGET_DEVICE_KIND = "TPU v5 lite"   # the chip the dry-run rooflines model


def peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; a kind without published
    peaks in ``PEAKS`` is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
