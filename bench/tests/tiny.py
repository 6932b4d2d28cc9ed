"""A tiny cell of each kind, small enough for the Pallas interpreter on a
CPU: the tests drive the whole harness on these, minus the look for a chip."""
from __future__ import annotations

import copy

from bench.run import BENCH, load_json

CFG = {
    "name": "tiny", "num_train_docs": 48, "num_test_docs": 64,
    "vocab_size": 384, "mean_doc_len": 24, "length_dist": "poisson",
    "length_sigma": None, "max_unique": 40, "num_topics": 8,
    "alpha0": 0.5, "beta0": 0.05, "kappa": 0.9, "tau": 1.0,
    "estep_max_iters": 30, "estep_tol": 1e-4,
    "estep_stream_dtype": "float32", "gen_topics": 8, "gen_alpha": 0.1,
    "gen_beta": 0.05}

# limits at this size, between the readings of sound runs (lam_step_gap
# up to 4.9e-4, pi_gap 8.4e-4, corr_gap_filled 2.0e-6, pi_gap_filled
# 2.2e-6, gamma_gap 2.3e-7) and of the control (8.2e-3, 1.0e-2, 3.9e-4,
# 6.8e-4, 4.0e-4) on the CPU, with a 0.1 s training window: the tiny corpus
# converges within a few passes, after which a step changes λ by rounding
TRAIN_LIMITS = {"lam_step_gap": 2e-3, "pi_gap": 2e-3,
                "corr_gap_filled": 1e-4, "pi_gap_filled": 1e-4}
TRAIN_SECONDS = 0.1
MIXES = {
    "arxiv-ivi-train": {"batch_size": 16, "limits": TRAIN_LIMITS},
    "arxiv-serve-burst": {"docs": 32, "rate_docs_s": 40.0,
                            "token_budget": 128,
                            "limits": {"gamma_gap": 2e-5}},
}


# each kind of cell the harness drives, by its traffic mix
TRAFFIC = {"arxiv-ivi-train": "ivi-padded",
           "arxiv-serve-burst": "serve-burst"}
E2E = {"arxiv-serve-burst": "serve_docs_per_s"}


def tiny_cell(workload: str):
    """A cell with the mix of ``workload``, at the tiny configuration's
    size: (a BENCHMARK.json-like spec, the cell, the config, the mix)."""
    mix = load_json(BENCH / "traffic" / f"{TRAFFIC[workload]}.json")
    mix.update(MIXES[workload])
    cell = {"name": workload, "config": "tiny",
            "traffic": TRAFFIC[workload], "chips": 1}
    e2e = E2E.get(workload, "train_tokens_per_s")
    bench = {"end_to_end": [
        {"name": e2e, "unit": "docs/s" if e2e == "serve_docs_per_s"
         else "tokens/s"},
        {"name": "setup_s", "unit": "s"}], "per_layer": []}
    return bench, cell, copy.deepcopy(CFG), mix
