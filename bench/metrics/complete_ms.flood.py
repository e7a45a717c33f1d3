"""Completion a batch (labels, stats, callbacks), flood cells, ms."""
from harness.spans import complete_ms


def read(run: dict):
    return complete_ms(run)
