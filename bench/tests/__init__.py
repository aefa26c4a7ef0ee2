"""CPU tests of the benchmark harness: the yardstick itself."""
