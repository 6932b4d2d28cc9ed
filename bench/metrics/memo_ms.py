"""Memo store (core/memo.py): per-step time of the program's
``train/memo_gather`` and ``train/memo_update`` spans. The update span ends
without a device sync, so it holds the host work and the enqueue."""
from bench.metrics._common import per_step_ms


def read(layer):
    return per_step_ms(layer, ("train/memo_gather", "train/memo_update"))
