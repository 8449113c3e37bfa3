"""The chip benchmark of the graph engine: see BENCHMARK.json and PERF.md."""
