"""Serving loop (serve/service.py): mean time of the program's
``serve/respond`` span, one per served batch: from the end of
``serve/request_batch`` on, the copy of γ to the host, the responses, the
latency accounting and the learner's intake."""


def read(layer):
    d = layer.span_durations("serve/respond")
    return 1e3 * sum(d) / len(d) if d else None
