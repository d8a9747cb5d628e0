"""The chip benchmark of the served SM-tree index (see BENCHMARK.json)."""
