"""apex_tpu.kernels — the Pallas kernel tier.

The reference Apex ships its L0 layer as CUDA extensions (``csrc/``:
fused optimizers, layer norm, attention, xentropy) that are simply
always on.  This package is the TPU rebuild's answer with the round-4/5
lesson baked in: a kernel is a *claim* that must be measured, so every
kernel here registers with :mod:`.dispatch` carrying a declared XLA
fallback, and which tier a call takes is a rule in the kernel's own
module — a pure function of the backend mode and the operands' shapes
and dtypes, with the measurement that justifies it cited beside it.
``docs/kernels.md`` is the catalog, including the negative results.

Import order matters: ``.dispatch`` first (the registry the kernel
modules register into), then the kernel modules, so partially-imported
cycles through ``ops.pallas`` compat imports always find the dispatch
surface already bound.
"""
from __future__ import annotations

from . import dispatch  # noqa: F401
from .dispatch import (  # noqa: F401
    MASKED_FILL,
    MASKED_LOGIT_THR,
    KernelSpec,
    catalog,
    force_mode,
    norm_kernel_mode,
    pallas_mode,
    register_kernel,
    run,
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
    "KernelSpec",
    "catalog",
    "force_mode",
    "fused_adam",
    "fused_sgd",
    "multi_tensor_adam",
    "multi_tensor_sgd",
    "norm_kernel_mode",
    "pallas_mode",
    "register_kernel",
    "run",
    "vocab_chain_loss",
]
