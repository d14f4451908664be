"""The chip benchmark of the range-retrieval server (see BENCHMARK.json)."""
