"""A cell at a size the CPU test run can hold: the shipped configuration
and traffic files with every width and length cut down, driven by the
same harness."""

from __future__ import annotations

import json

from bench.harness import ROOT, load_module

BENCH = {
    "end_to_end": [
        {"name": "ttft_p90_ms", "unit": "ms"},
        {"name": "itl_p95_ms", "unit": "ms"},
        {"name": "output_tok_s", "unit": "tokens/s"},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [
        {"name": n, "unit": "%", "moves": "ttft_p90_ms"}
        for n in ("submit_lag_p99_ms", "queue_wait_p90_ms",
                  "prefix_hit_share", "local_frac.ep4",
                  "prefill_tokens_per_call", "prefill_wait_p90_ms",
                  "host_tick_self_ms", "decode_expert_ffn_roofline")
    ],
}


def cell(chips: int = 1, config: str = "mixtral-8x7b-2l", traffic: str = "chat"):
    """(benchmark, cell, configuration, traffic), tiny."""
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    conf.update(load_module(conf["arch"]).TINY)
    tr = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())
    tr.update(rate_per_s=8.0, warm_seconds=1.0, prompt_len=[128, 0.8, 16, 256],
              output_len=[24, 0.6, 8, 48],
              serving={**tr["serving"], "max_slots": 4, "block_size": 8,
                       "review_rounds": 4},
              check={"requests": 8})
    if chips > 1:
        tr.update(origins=chips, origin_skew=1.0, origin_tokens={"zipf": 1.1})
    c = {"name": "tiny", "config": config, "traffic": traffic, "chips": chips}
    return BENCH, c, conf, tr
