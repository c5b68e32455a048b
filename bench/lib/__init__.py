"""Harness pieces shared by every cell: description loading, traffic,
weights, the served window, trace reduction and the correctness check."""
