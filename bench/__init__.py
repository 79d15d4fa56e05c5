"""The on-chip benchmark of this repository; see BENCHMARK.json and PERF.md."""
