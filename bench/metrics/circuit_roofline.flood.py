"""Circuit evaluation's share of the HBM roofline, serving flood cells, %."""
from harness.readers import circuit_roofline


def read(run: dict):
    return circuit_roofline(run)
