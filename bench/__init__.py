"""Chip benchmark of the MoE serving system (see ``BENCHMARK.json``)."""
