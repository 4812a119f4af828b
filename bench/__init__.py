"""The chip benchmark of the multi-tenant monitor (see BENCHMARK.json)."""
