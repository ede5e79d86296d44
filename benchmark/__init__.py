"""Chip benchmark of arrow-matrix-tpu: one cell of BENCHMARK.json per
process (``python -m benchmark --workload <cell> ...``)."""
