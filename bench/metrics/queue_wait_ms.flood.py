"""Oldest request's queue wait a batch, flood cells, ms."""
from harness.spans import queue_wait_ms


def read(run: dict):
    return queue_wait_ms(run)
