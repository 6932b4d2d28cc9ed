"""Serving loop (serve/service.py, serve/admission.py): median over
requests of (start of the batch that served it − its scheduled arrival)."""
import statistics


def read(layer):
    waits = [r["start_s"] - r["arrival_s"] for r in layer.requests
             if r.get("start_s") is not None]
    return 1e3 * statistics.median(waits) if waits else None
