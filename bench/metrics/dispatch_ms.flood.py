"""Mean fleet dispatch time under closed-loop flood traffic, ms."""
from harness.readers import dispatch_ms


def read(run: dict):
    return dispatch_ms(run)
