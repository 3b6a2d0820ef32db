"""On-chip serving benchmark of this repository (see BENCHMARK.json)."""
