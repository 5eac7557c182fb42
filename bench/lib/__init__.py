"""The harness: traffic, weights, the measured window, trace reduction,
the plain reference and the correctness check."""
