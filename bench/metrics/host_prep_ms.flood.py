"""Host preparation a fleet dispatch (gather to plan), flood cells, ms."""
from harness.spans import host_prep_ms


def read(run: dict):
    return host_prep_ms(run)
