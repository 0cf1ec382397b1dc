"""apex_tpu.kernels — the measured Pallas kernel tier.

The reference Apex ships its L0 layer as CUDA extensions (``csrc/``:
fused optimizers, layer norm, attention, xentropy) that are simply
always on.  This package is the TPU rebuild's answer with the round-4/5
lesson baked in: a kernel is a *claim* that must be measured, so every
kernel here registers with :mod:`.dispatch` carrying a declared XLA
fallback and a threshold probe, dispatch consults the on-disk
calibration ledger (:mod:`.ledger`, keyed by chip + shape fingerprint)
at trace time, and anything below its measured win region runs XLA.
``docs/kernels.md`` is the catalog, including the negative results.

Import order matters: ``.dispatch`` first (the registry the kernel
modules register into), then the kernel modules, so partially-imported
cycles through ``ops.pallas`` compat imports always find the dispatch
surface already bound.
"""
from __future__ import annotations

from . import ledger  # noqa: F401
from . import dispatch  # noqa: F401
from .dispatch import (  # noqa: F401
    MASKED_FILL,
    MASKED_LOGIT_THR,
    Decision,
    KernelSpec,
    attention_fp,
    catalog,
    decide,
    decisions,
    force_mode,
    measured_threshold,
    multi_tensor_fp,
    norm_kernel_mode,
    pallas_mode,
    parse_fp,
    register_kernel,
    reset_decisions,
    run,
    shape_fp,
    vocab_chain_fp,
)
from .ledger import (  # noqa: F401
    Ledger,
    chip_name,
    get_ledger,
    set_path as set_ledger_path,
)

# kernel modules (each registers itself with dispatch on import)
from . import attention  # noqa: F401
from . import layer_norm  # noqa: F401
from . import rms_norm  # noqa: F401
from . import xentropy  # noqa: F401
from . import lm_head_xent  # noqa: F401
from . import multi_tensor  # noqa: F401
from . import vocab_chain  # noqa: F401
from . import spec_verify  # noqa: F401
from . import paged_attention  # noqa: F401

from .multi_tensor import (  # noqa: F401
    fused_adam,
    fused_sgd,
    multi_tensor_adam,
    multi_tensor_sgd,
)
from .vocab_chain import vocab_chain_loss  # noqa: F401

__all__ = [
    "MASKED_FILL",
    "MASKED_LOGIT_THR",
    "Decision",
    "KernelSpec",
    "Ledger",
    "attention_fp",
    "catalog",
    "chip_name",
    "decide",
    "decisions",
    "force_mode",
    "fused_adam",
    "fused_sgd",
    "get_ledger",
    "measured_threshold",
    "multi_tensor_adam",
    "multi_tensor_fp",
    "multi_tensor_sgd",
    "norm_kernel_mode",
    "pallas_mode",
    "parse_fp",
    "register_kernel",
    "reset_decisions",
    "run",
    "set_ledger_path",
    "shape_fp",
    "vocab_chain_fp",
    "vocab_chain_loss",
]
