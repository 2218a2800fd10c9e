"""CPU tests of the benchmark harness (the chip runs are in PERF.md)."""
