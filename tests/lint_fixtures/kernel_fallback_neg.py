"""KERNEL-FALLBACK negative fixture: model code consumes the kernels
tier through its dispatch surface, and registrations declare the XLA
fallback."""
import jax.numpy as jnp

from apex_tpu.kernels import attention as _k
from apex_tpu.kernels.dispatch import register_kernel


def model_path(q, k, v):
    # the sanctioned route: the kernel's module decides pallas-vs-XLA
    # from mode and shape; no raw pallas_call in model code
    return _k.flash_attention_fwd(q, k, v, None, 1.0, True)


register_kernel(
    "well_declared_kernel",
    xla_fallback="apex_tpu.contrib.multihead_attn.attn_funcs."
                 "attention_reference",
    doc="fixture: compliant registration")
