"""The comparison that decides ``correct``, rehearsed on the CPU at a tiny
size: a sound run passes, its float8 control fails, and each fault the
one-chip cell can have (a token altered where it is produced, a decode
step that leaves the KV state unchanged) comes out not correct."""

import time

import pytest

from bench import faults, harness, tiny

SEED = 2**33 + 11


def _run(control=False, trace=False):
    return harness.run("tiny", SEED, 2.0, trace, t_start=time.perf_counter(),
                       chips_check=False, cell_override=tiny.cell(1),
                       control=control, log=lambda *a, **k: None)


@pytest.fixture(scope="module")
def sound():
    return _run(control=True, trace=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert list(sound)[-1] == "checks"
    assert set(sound["checks"]) == {"mean_gap"}
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["device"]["platform"] == "cpu"
    # traced: the per-layer metrics the CPU can give (no device trace here)
    assert {"submit_lag_p99_ms", "queue_wait_p90_ms",
            "prefix_hit_share"} <= set(sound["metrics"])


def test_control_is_not_correct(sound):
    """The reference in float8 in the program's place comes out not
    correct by the run's own decision, on the same limits."""
    ctl = sound["control"]
    assert ctl["correct"] is False
    assert set(ctl["checks"]) == set(sound["checks"])


@pytest.mark.parametrize("fault", ["token", "state"])
def test_fault_is_not_correct(fault):
    with faults.planted(fault):
        r = _run()
    assert r["correct"] is False
