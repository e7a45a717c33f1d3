"""Fleet walk's share of the HBM roofline, zoo serving cells, %."""
from harness.readers import circuit_roofline


def read(run: dict):
    return circuit_roofline(run)
