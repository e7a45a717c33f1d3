"""The benchmark harness: drivers, references, trace reduction."""
