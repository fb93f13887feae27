"""The chip benchmark of CIND discovery (see BENCHMARK.json and PERF.md)."""
