"""Observability benchmark: measured-vs-modeled accounting → BENCH_obs.json.

Three sections, all driven through the public ``repro.obs`` surface:

* **roofline** — the measured-vs-modeled join the ROADMAP asked for.
  A short IVI run on the Pallas E-step backend records device-synced
  ``train/solve`` spans (`SpanRecorder(device_sync=True)`); their min
  wall time joins against the kernels' structural HBM-byte model
  (`kernel_bench.modeled_estep_hbm_bytes`) under the seed roofline
  hardware table (`repro.obs.roofline.HW`, re-exported by the
  seed harness) via
  ``repro.obs.roofline_from_trace``. On this CPU container the kernels
  run in interpret mode, so the record carries ``proxy_regime: true``
  and the agreement flag is informational; on a TPU the same record is
  the model-validation gate (docs/observability.md §roofline).

* **roofline_csr** — the same join for the flat-token CSR layout
  (``LDA(layout="csr")``): its ``train/solve`` spans are priced by
  ``kernel_bench.modeled_estep_csr_hbm_bytes`` at the engine's
  budget-sized stream shape, so the width-free path carries its own
  measured-vs-modeled record (and ``proxy_regime`` flag) in
  BENCH_obs.json alongside the padded one.

* **overhead** — the telemetry cost contract. The same streaming
  training smoke runs telemetry-off and telemetry-on (default bundle:
  spans + metrics + evaluate-cadence watchdog), min-of-3 each. The CI
  bars: bit-identical final λ (telemetry must not perturb the
  trajectory) and ≤5% wall-clock overhead (CPU wall time is noisy at
  smoke scale, hence min-of-3 and a ≥1s workload).

* **trace_roundtrip** — the roofline run's trace dumps to JSONL,
  re-validates against the schema, and converts to a Chrome trace with a
  count-exact event match.

Run: ``PYTHONPATH=src python -m benchmarks.obs_bench [--json BENCH_obs.json]``
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax
import numpy as np

from benchmarks.kernel_bench import (modeled_estep_csr_hbm_bytes,
                                     modeled_estep_hbm_bytes)
from repro.obs.roofline import HW
from repro.data import PAPER_CORPORA, make_corpus
from repro.lda import LDA
from repro.obs import (SpanRecorder, Telemetry, chrome_trace_from_jsonl,
                       roofline_from_trace, validate_jsonl)
from repro.launch.compile_cache import enable_compile_cache

ESTEP_ITERS = 15
BATCH = 32


def _proxy_regime() -> bool:
    """Interpret-mode CPU measurements are Python-time proxies, not the
    HBM-model's hardware — only a real accelerator validates the model."""
    return jax.devices()[0].platform not in ("tpu", "gpu")


def roofline_section(corpus_name: str = "tiny") -> tuple[dict, Telemetry]:
    """Measured train/solve spans joined against the modeled HBM bytes."""
    spec = PAPER_CORPORA[corpus_name]
    corpus = make_corpus(spec, split="train", seed=0)
    tel = Telemetry(trace=SpanRecorder(device_sync=True))
    lda = LDA(num_topics=spec.num_topics, vocab_size=spec.vocab_size,
              estep_max_iters=ESTEP_ITERS, estep_backend="pallas",
              algo="ivi", batch_size=BATCH, seed=0, telemetry=tel)
    lda.fit(corpus, epochs=2)    # epoch 2: every solve is a warm jit entry
    b, v, k, l = (BATCH, spec.vocab_size, spec.num_topics,
                  corpus.max_unique)
    modeled = {
        # the fused Pallas path is what estep_backend="pallas" dispatches
        "train/solve": modeled_estep_hbm_bytes("fused", b, v, k, l,
                                               ESTEP_ITERS),
    }
    check = roofline_from_trace(
        tel.trace.records, modeled, hbm_gbps=HW["hbm_bw"] / 1e9,
        proxy_regime=_proxy_regime())
    check["shape"] = {"B": b, "V": v, "K": k, "L": l,
                      "sweeps": ESTEP_ITERS,
                      "platform": jax.devices()[0].platform}
    return check, tel


def roofline_csr_section(corpus_name: str = "tiny") -> dict:
    """The roofline join for the flat-token CSR layout: a short streaming
    run with ``layout="csr"`` on the Pallas backend, its ``train/solve``
    spans priced by the CSR HBM model at the engine's (token_budget,)
    stream shape — every batch shares ONE compiled entry, so the span
    population is homogeneous by construction."""
    from repro.data.stream import CorpusDocStream

    spec = PAPER_CORPORA[corpus_name]
    corpus = make_corpus(spec, split="train", seed=0)
    tel = Telemetry(trace=SpanRecorder(device_sync=True))
    lda = LDA(num_topics=spec.num_topics, vocab_size=spec.vocab_size,
              estep_max_iters=ESTEP_ITERS, estep_backend="pallas",
              algo="ivi", batch_size=BATCH, layout="csr", seed=0,
              telemetry=tel)
    lda.fit(CorpusDocStream(corpus), epochs=2)   # epoch 2: warm entries
    t = lda.trainer.eng.token_budget             # engine-resolved default
    b, v, k = BATCH, spec.vocab_size, spec.num_topics
    modeled = {
        "train/solve": modeled_estep_csr_hbm_bytes(t, b, v, k,
                                                   ESTEP_ITERS),
    }
    check = roofline_from_trace(
        tel.trace.records, modeled, hbm_gbps=HW["hbm_bw"] / 1e9,
        proxy_regime=_proxy_regime())
    check["shape"] = {"T": t, "B": b, "V": v, "K": k,
                      "sweeps": ESTEP_ITERS,
                      "platform": jax.devices()[0].platform}
    return check


def _timed_stream_fit(telemetry) -> tuple[float, np.ndarray, object]:
    """One streaming training smoke; returns (seconds, final λ, bundle)."""
    from repro.data.stream import CorpusDocStream

    # "small" at a deep E-step: enough device work per batch (~10ms) that
    # the fixed per-batch recorder cost (~0.2ms: 4 spans + a handful of
    # counter updates) amortizes the way it does at production shapes —
    # "tiny" at shallow sweeps would measure Python overhead against
    # nothing and the bar would gate on scheduler noise
    spec = PAPER_CORPORA["small"]
    corpus = make_corpus(spec, split="train", seed=0)
    stream = CorpusDocStream(corpus)
    lda = LDA(num_topics=spec.num_topics, vocab_size=spec.vocab_size,
              estep_max_iters=80, algo="ivi", batch_size=32, seed=0,
              telemetry=telemetry)
    t0 = time.perf_counter()
    lda.fit(stream, epochs=4)
    jax.block_until_ready(lda.lam)
    return time.perf_counter() - t0, np.asarray(lda.lam), lda.telemetry


def overhead_section(repeats: int = 3) -> dict:
    """Telemetry-off vs telemetry-on streaming smoke: bit-equality of the
    trajectory plus the wall-clock overhead bar (min-of-N per arm)."""
    off_s, on_s = [], []
    lam_off = lam_on = None
    tel_stats = None
    for _ in range(repeats):
        s, lam_off, _ = _timed_stream_fit(None)
        off_s.append(s)
        s, lam_on, tel = _timed_stream_fit(True)
        on_s.append(s)
        tel_stats = {
            "span_records": tel.trace.num_records,
            "train_tokens": tel.metrics.total("train.tokens"),
            "pack_batches": tel.metrics.total("pack.batches"),
        }
    t_off, t_on = min(off_s), min(on_s)
    return {
        "repeats": repeats,
        "telemetry_off_s": t_off,
        "telemetry_on_s": t_on,
        "overhead_pct": (t_on - t_off) / t_off * 100.0,
        "lam_bit_identical": bool(np.array_equal(lam_off, lam_on)),
        "telemetry_on_stats": tel_stats,
        "note": ("min-of-N CPU wall time; the ≤5% bar is asserted on the "
                 "min to stay below scheduler noise at smoke scale"),
    }


def trace_roundtrip_section(tel: Telemetry, out_dir: str) -> dict:
    """Dump → validate → Chrome-convert the roofline run's trace."""
    jsonl = os.path.join(out_dir, "obs_trace.jsonl")
    chrome = os.path.join(out_dir, "obs_trace.chrome.json")
    dumped = tel.trace.dump_jsonl(jsonl)
    validated = validate_jsonl(jsonl)
    chrome_events = chrome_trace_from_jsonl(jsonl, chrome)
    return {
        "jsonl": jsonl,
        "chrome": chrome,
        "records_dumped": dumped,
        "records_validated": validated,
        "chrome_events": chrome_events,
        "count_exact": dumped == validated == chrome_events,
    }


def obs_report(json_path: str | None = None, *,
               repeats: int = 3) -> dict:
    roofline, tel = roofline_section()
    record = {
        "roofline": roofline,
        "roofline_csr": roofline_csr_section(),
        "overhead": overhead_section(repeats=repeats),
        "trace_roundtrip": trace_roundtrip_section(
            tel, tempfile.mkdtemp(prefix="obs_bench_")),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(record, f, indent=2)
    return record


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="BENCH_obs.json",
                    help="where to write the observability record")
    ap.add_argument("--repeats", type=int, default=3,
                    help="min-of-N repeats for the overhead arms")
    args = ap.parse_args()
    rec = obs_report(args.json, repeats=args.repeats)
    rl, ov, tr = rec["roofline"], rec["overhead"], rec["trace_roundtrip"]
    rc = rec["roofline_csr"]
    r0, c0 = rl["records"][0], rc["records"][0]
    print(f"BENCH_obs -> {args.json}")
    print(f"  roofline : {rl['n_records']} record(s) on "
          f"{rl['shape']['platform']} (proxy_regime={rl['proxy_regime']}); "
          f"{r0['name']}: measured {r0['measured_s'] * 1e3:.2f}ms vs "
          f"modeled {r0['modeled_s'] * 1e3:.4f}ms "
          f"({r0['measured_vs_modeled']:.1f}x, {r0['verdict']})")
    print(f"  roofline_csr : T={rc['shape']['T']} "
          f"(proxy_regime={rc['proxy_regime']}); "
          f"{c0['name']}: measured {c0['measured_s'] * 1e3:.2f}ms vs "
          f"modeled {c0['modeled_s'] * 1e3:.4f}ms "
          f"({c0['measured_vs_modeled']:.1f}x, {c0['verdict']})")
    print(f"  overhead : off {ov['telemetry_off_s']:.2f}s vs on "
          f"{ov['telemetry_on_s']:.2f}s -> {ov['overhead_pct']:+.2f}% "
          f"(lam bit-identical: {ov['lam_bit_identical']}, "
          f"{ov['telemetry_on_stats']['span_records']} spans)")
    print(f"  trace    : {tr['records_dumped']} records -> "
          f"{tr['chrome_events']} Chrome events "
          f"(count_exact={tr['count_exact']})")
    assert rl["n_records"] >= 1 and not rl["missing_spans"], \
        "roofline join produced no measured-vs-modeled record"
    assert rc["n_records"] >= 1 and not rc["missing_spans"], \
        "CSR roofline join produced no measured-vs-modeled record"
    assert ov["lam_bit_identical"], \
        "telemetry-on run diverged from the telemetry-off trajectory"
    assert ov["overhead_pct"] <= 5.0, \
        f"telemetry overhead {ov['overhead_pct']:.2f}% exceeds the 5% bar"
    assert tr["count_exact"], \
        "trace JSONL -> Chrome conversion lost or invented records"
