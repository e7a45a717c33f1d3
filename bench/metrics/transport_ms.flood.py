"""Transport time a frame (decode, admit, write), flood cells, ms."""
from harness.spans import transport_ms


def read(run: dict):
    return transport_ms(run)
