"""The benchmark: harness, traffic kinds, reference, trace reduction."""
