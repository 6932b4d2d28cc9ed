"""Serving loop (serve/service.py, serve/admission.py): mean time of the
program's ``serve/admit`` span, one per offered request: the offer to the
admission controller and the packer's ``add``, which may emit a batch."""


def read(layer):
    d = layer.span_durations("serve/admit")
    return 1e3 * sum(d) / len(d) if d else None
