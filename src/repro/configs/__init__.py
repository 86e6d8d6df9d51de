from repro.configs.base import (ModelConfig, InputShape, INPUT_SHAPES,
                                get_config, list_configs, register)

# the architectures the multi-pod dry-run sweeps (launch/dryrun.py --all)
ASSIGNED_ARCHS = [
    "starcoder2-3b", "qwen2-vl-72b", "tinyllama-1.1b", "falcon-mamba-7b",
    "zamba2-2.7b", "musicgen-large", "command-r-plus-104b",
    "llama4-maverick-400b-a17b", "yi-6b", "phi3.5-moe-42b-a6.6b",
]
