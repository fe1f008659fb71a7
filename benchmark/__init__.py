"""The benchmark: harness, yardstick and plain references (see PERF.md)."""
