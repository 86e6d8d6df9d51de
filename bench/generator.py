"""The one traffic generator: turns a traffic file's parameters and a run's
seed into timed requests.

The sampling follows ``repro.serving.workload.WorkloadStream`` (clipped
lognormal prompt and output lengths, Poisson arrivals, Zipf-skewed
origins, a token profile per origin), with one change: the arrivals of
each segment of a run (the warm traffic, then the measured window), their
times, prompt and output lengths and origins, are drawn once from the
traffic file's own ``shape_seed``, and the run's seed draws only the
tokens. Every seed then offers the window the same work on the same
timeline (a Poisson stream conditioned on its count in each segment), so
that what a run measures does not swing with where a seed happens to put
the long prompts.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Timed:
    """One request of the stream: due ``at`` seconds after traffic starts."""

    at: float
    prompt: np.ndarray
    max_new_tokens: int
    origin: int | None


def _lognormal(rng, n: int, spec) -> np.ndarray:
    median, sigma, lo, hi = spec
    z = rng.standard_normal(n)
    return np.clip(np.round(float(median) * np.exp(sigma * z)), lo, hi).astype(int)


def max_len(traffic: dict) -> int:
    """KV positions a request may hold: the longest prompt plus the
    longest output, and the serving CLI's 8 spare positions."""
    return int(traffic["prompt_len"][3] + traffic["output_len"][3] + 8)


def _token_profile(traffic: dict, origin: int, vocab: int):
    """Token probabilities of one origin's task (None: uniform). A Zipf
    law over a permutation of the vocabulary fixed by the traffic file,
    so each origin favours its own tokens and so its own experts."""
    prof = traffic.get("origin_tokens")
    if not prof:
        return None
    rng = np.random.default_rng([traffic["shape_seed"], origin])
    p = 1.0 / (np.arange(vocab) + 1.0) ** prof["zipf"]
    p = p[np.argsort(rng.permutation(vocab))]
    return p / p.sum()


def fixed_set(traffic: dict, seconds: float, segment: int) -> dict:
    """The requests of one segment of a run (0: the warm traffic, 1: the
    measured window), fixed by the traffic file's ``shape_seed``: their
    count (the rate times the segment's seconds), the gaps between them
    (exponential, scaled to span the segment), and the prompt and output
    lengths and origins."""
    shape = np.random.default_rng([traffic["shape_seed"], segment])
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    gaps = shape.exponential(1.0, n + 1)
    n_org = int(traffic.get("origins", 1))
    w = (np.arange(n_org) + 1.0) ** -float(traffic.get("origin_skew", 0.0))
    return {
        "gaps": gaps * (seconds / gaps.sum()),
        "prompts": _lognormal(shape, n, traffic["prompt_len"]),
        "outputs": _lognormal(shape, n, traffic["output_len"]),
        "origins": shape.choice(n_org, size=n, p=w / w.sum()),
    }


def stream(traffic: dict, seed: int, warm_s: float, window_s: float,
           vocab: int) -> list[Timed]:
    """The requests of a run, in arrival order: the warm segment's in
    ``[0, warm_s)``, then the window's in ``[warm_s, warm_s + window_s)``.
    Each segment offers its fixed set in the order it was drawn; the seed
    draws each prompt's tokens."""
    rng = np.random.default_rng(seed)
    n_org = int(traffic.get("origins", 1))
    profiles = [_token_profile(traffic, o, vocab) for o in range(n_org)]
    out = []
    for segment, (t0, seconds) in enumerate(((0.0, warm_s), (warm_s, window_s))):
        fs = fixed_set(traffic, seconds, segment)
        n = len(fs["prompts"])
        at = t0 + np.cumsum(fs["gaps"])[:n]
        for j in range(n):
            org = int(fs["origins"][j])
            p = profiles[org]
            size = int(fs["prompts"][j])
            toks = (rng.integers(0, vocab, size) if p is None
                    else rng.choice(vocab, size, p=p))
            out.append(Timed(at=float(at[j]), prompt=toks.astype(np.int32),
                             max_new_tokens=int(fs["outputs"][j]),
                             origin=org if n_org > 1 else None))
    return out
