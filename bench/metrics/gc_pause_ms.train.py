"""Host runtime, training cells: summed time of the program's ``py/gc``
spans (the garbage collector's pauses, on any thread) in the traced
window."""


def read(layer):
    d = layer.span_durations("py/gc")
    return 1e3 * sum(d) if d and layer.steps else None
