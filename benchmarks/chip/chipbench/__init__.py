"""The yardstick of the on-chip benchmark: data, traffic, reference, trace reduction."""
