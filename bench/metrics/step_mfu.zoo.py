"""Whole step's share of the int8 peak, zoo serving cells, %."""
from harness.readers import step_mfu


def read(run: dict):
    return step_mfu(run)
