"""Chip benchmark of Cluster-GCN training: cells named in BENCHMARK.json,
run one at a time by `python3 bench/run.py`."""
