"""Device round trip a fleet dispatch (in, launch, fetch), flood, ms."""
from harness.spans import device_roundtrip_ms


def read(run: dict):
    return device_roundtrip_ms(run)
