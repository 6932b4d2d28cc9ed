"""Inferencer (lda/infer.py): mean time of a served batch, the program's
``serve/request_batch`` span, which runs ``posterior_packed`` and blocks on
γ. (``serve/solve`` is never synced, so it holds only the dispatch.)"""


def read(layer):
    d = layer.span_durations("serve/request_batch")
    return 1e3 * sum(d) / len(d) if d else None
