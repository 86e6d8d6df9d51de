"""The roofline: the least time a step's needed work takes on the chips,
over the time it took.

The needed work itself (operations and bytes of a decode or chunk call,
and of each program scope in it) is the architecture's, counted by the
``work`` function of the module a configuration's ``arch`` key names.
"""

from __future__ import annotations


def roofline(work: dict, seconds: float, peaks: dict, chips: int) -> dict:
    """Least time over measured time, and which bound sets the least time."""
    t_flops = work["flops"] / (peaks["bf16_flops"] * chips)
    t_bytes = work["bytes"] / (peaks["hbm_bytes_per_s"] * chips)
    least = max(t_flops, t_bytes)
    return {"share": least / seconds,
            "bound": "flops" if t_flops >= t_bytes else "hbm"}
