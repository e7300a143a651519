"""Plain references the benchmark compares the served tokens with."""
