"""repro.tune — autotuned kernel policies for the LDA E-step stack.

The policy space (``KernelPolicy``, defined in ``repro.core.types`` so a
tuned config can ride on the frozen, jit-static ``LDAConfig``):

* fused padded fixed point: ``block_b`` / ``block_v``;
* memo_delta scatter pair: ``delta_block_b`` / ``delta_block_v`` /
  ``pi_block_l`` / ``scatter_block_t``;
* CSR flat-token path: ``block_t``;
* memo wire dtype and the serving double-buffer depth.

Winners live in a versioned on-disk store (``PolicyStore``) keyed on
``(backend, layout, B_or_T, V, K, W, device_kind)``; engines and the
serving path resolve them through a ``PolicyResolver`` (telemetry:
``tune.cache`` hit/miss counters, ``tune/lookup`` spans). With no store
configured everything resolves to the built-in defaults and the whole
stack is bit-identical to the pre-autotune behaviour.

Search (``repro.tune.search``) is deliberately imported lazily — it
pulls in the kernels; the store/resolve layer is dependency-light so
engines can import it at construction. CLI: ``python -m repro.tune``
(tune / show / clear). docs/tuning.md has the full story, including why
an off-chip objective is a model, never a measurement.
"""
from __future__ import annotations

from repro.core.types import DEFAULT_KERNEL_POLICY, KernelPolicy

from .resolve import PolicyResolver
from .store import (
    STORE_FORMAT,
    STORE_VERSION,
    PolicyKey,
    PolicyStore,
    TuneStoreWarning,
    as_store,
    current_device_kind,
    policy_from_dict,
    policy_to_dict,
)

__all__ = [
    "KernelPolicy", "DEFAULT_KERNEL_POLICY",
    "PolicyKey", "PolicyStore", "PolicyResolver", "TuneStoreWarning",
    "STORE_FORMAT", "STORE_VERSION",
    "as_store", "current_device_kind",
    "policy_from_dict", "policy_to_dict",
]
