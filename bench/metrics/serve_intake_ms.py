"""Serving loop (serve/service.py): median over offered requests of the
``lag_ms`` attr of the program's ``serve/admit`` span, the time from a
request's scheduled arrival to its offer, while the loop was busy."""
import statistics


def read(layer):
    lags = [r["attrs"]["lag_ms"] for r in layer.spans
            if r.get("type") == "span" and r["name"] == "serve/admit"
            and "lag_ms" in r.get("attrs", {})]
    return statistics.median(lags) if lags else None
