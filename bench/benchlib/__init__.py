"""The benchmark harness: catalog, system under test, window, trace
reduction, yardstick and the correctness check."""
