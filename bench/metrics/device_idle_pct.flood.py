"""Device idle share of the traced window, flood cells, %."""
from harness.readers import device_idle_pct


def read(run: dict):
    return device_idle_pct(run)
