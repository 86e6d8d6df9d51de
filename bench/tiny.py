"""A cell at a size the CPU test run can hold: the shipped configuration
and traffic files with every width and length cut down, driven by the
same harness."""

from __future__ import annotations

import json

from bench.harness import ROOT

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
                  "prefix_hit_share", "local_frac.ep4")
    ],
}


def cell(chips: int = 1, config: str = "mixtral-8x7b-2l", traffic: str = "chat"):
    """(benchmark, cell, configuration, traffic), tiny."""
    conf = json.loads((ROOT / "bench" / "configs" / f"{config}.json").read_text())
    # the published vocabulary and expert count stay: a float8 control
    # departs from the reference through near-ties among many logits
    conf.update(hidden_size=128, intermediate_size=256, num_attention_heads=4,
                num_key_value_heads=2, num_hidden_layers=2)
    tr = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json").read_text())
    tr.update(rate_per_s=8.0, warm_seconds=1.0, prompt_len=[12, 0.8, 4, 40],
              output_len=[8, 0.6, 2, 16],
              serving={**tr["serving"], "max_slots": 4, "block_size": 8,
                       "review_rounds": 4},
              check={"requests": 4})
    if chips > 1:
        tr.update(origins=chips, origin_skew=1.0, origin_tokens={"zipf": 1.1})
    c = {"name": "tiny", "config": config, "traffic": traffic, "chips": chips}
    return BENCH, c, conf, tr
