"""One reader per per-layer metric, loaded by the name in BENCHMARK.json."""
