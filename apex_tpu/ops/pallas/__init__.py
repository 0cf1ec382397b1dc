"""Compatibility shim — the Pallas kernels moved to
:mod:`apex_tpu.kernels` (the kernel tier; each module holds its own
dispatch rule).

This package re-exports the dispatch surface the old location provided
(``pallas_mode``/``force_mode``/``norm_kernel_mode`` and the
masked-vocabulary constants) and aliases the old submodule paths
(``apex_tpu.ops.pallas.attention`` etc.) onto the moved modules, so
existing ``from apex_tpu.ops.pallas.attention import ...`` imports keep
resolving to the SAME module objects.  New code should import from
:mod:`apex_tpu.kernels` directly.
"""
from __future__ import annotations

import sys

from ...kernels import (attention, layer_norm, lm_head_xent, rms_norm,
                        xentropy)
from ...kernels.dispatch import (  # noqa: F401
    MASKED_FILL,
    MASKED_LOGIT_THR,
    force_mode,
    norm_kernel_mode,
    pallas_mode,
)

for _name, _mod in (("attention", attention), ("layer_norm", layer_norm),
                    ("rms_norm", rms_norm), ("xentropy", xentropy),
                    ("lm_head_xent", lm_head_xent)):
    sys.modules[__name__ + "." + _name] = _mod
del _name, _mod

__all__ = [
    "MASKED_FILL",
    "MASKED_LOGIT_THR",
    "attention",
    "force_mode",
    "layer_norm",
    "lm_head_xent",
    "norm_kernel_mode",
    "pallas_mode",
    "rms_norm",
    "xentropy",
]
