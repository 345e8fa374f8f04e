"""Chip benchmark of the served text-to-image path (see BENCHMARK.json)."""
