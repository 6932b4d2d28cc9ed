"""Device, training cells: 1 − (union of device-busy intervals) / (traced
window), averaged over the chips, in %."""
from bench.metrics._common import idle_pct


def read(layer):
    return idle_pct(layer) if layer.steps else None
