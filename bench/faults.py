"""Faults planted under the timed path, for the tests that show the
comparison catches them. Each is a context manager that patches the
program for the runs made inside it.

    token     a served token altered where it is produced (the sampler)
    state     a decode step that returns the KV pool unchanged
    exchange  the exchange between chips left out of the EP MoE layer
              (its all-to-all and its psum return their input)

Run as a script (``python -m bench.faults <fault> <chips>``) it makes one
sound and one faulted run of the tiny cell and prints both results; the
tests run it in a child process where four chips must be faked.
"""

from __future__ import annotations

import contextlib
import json
import sys
import time
import types


@contextlib.contextmanager
def planted(name: str):
    from repro.models import moe, transformer
    from repro.serving import engine

    if name == "token":
        real = engine.sample_tokens

        def altered(logits, *a, **k):
            return (real(logits, *a, **k) + 1) % logits.shape[-1]

        target, attr, fake = engine, "sample_tokens", altered
    elif name == "state":
        real = transformer.decode_step

        def unchanged(rt, params, cache, *a, **k):
            logits, _, stats = real(rt, params, cache, *a, **k)
            return logits, cache, stats

        target, attr, fake = transformer, "decode_step", unchanged
    elif name == "exchange":
        real = moe.lax
        fake = types.SimpleNamespace(**{k: getattr(real, k) for k in dir(real)
                                        if not k.startswith("__")})
        fake.psum = lambda x, axes: x
        fake.all_to_all = lambda x, *a, **k: x
        target, attr = moe, "lax"
    else:
        raise ValueError(f"unknown fault {name!r}")
    setattr(target, attr, fake)
    try:
        yield
    finally:
        setattr(target, attr, real)


def tiny_runs(fault: str, chips: int, seed: int = 2**33 + 11) -> dict:
    """A sound run and a faulted run of the tiny cell on this host's CPU."""
    from bench import harness, tiny

    out = {}
    for label in ("sound", fault):
        ctx = planted(fault) if label == fault else contextlib.nullcontext()
        with ctx:
            r = harness.run("tiny", seed, 2.0, False,
                            t_start=time.perf_counter(), chips_check=False,
                            cell_override=tiny.cell(chips), log=_quiet)
        out[label] = {"correct": r["correct"], "checks": r["checks"]}
    return out


def _quiet(*a, **k):
    pass


if __name__ == "__main__":
    print(json.dumps(tiny_runs(sys.argv[1], int(sys.argv[2]))))
