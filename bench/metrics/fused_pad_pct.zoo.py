"""Padding share of the fused launches' walked gate steps, zoo cells, %."""
from harness.fused import fused_pad_pct


def read(run: dict):
    return fused_pad_pct(run)
