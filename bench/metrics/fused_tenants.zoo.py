"""Mean tenants a fused megakernel launch carried, zoo cells."""
from harness.fused import fused_tenants


def read(run: dict):
    return fused_tenants(run)
