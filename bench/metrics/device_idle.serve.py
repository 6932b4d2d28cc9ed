"""Device, serving cells: 1 − (union of device-busy intervals) / (traced
window), in %."""
from bench.metrics._common import idle_pct


def read(layer):
    return idle_pct(layer) if layer.requests else None
