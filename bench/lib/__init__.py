"""Shared yardstick of the benchmark: files by name, traffic, statistics,
peaks, operation counts, seeded weights and the trace reduction."""
