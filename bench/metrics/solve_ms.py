"""Engine step (core/engines.py): per-step time of the program's
``train/solve`` span, which ends on a device sync of λ."""
from bench.metrics._common import per_step_ms


def read(layer):
    return per_step_ms(layer, ("train/solve",))
