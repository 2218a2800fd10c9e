"""On-chip benchmark of the federated round (see BENCHMARK.json, PERF.md)."""
