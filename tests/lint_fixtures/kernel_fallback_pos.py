"""KERNEL-FALLBACK positive fixture: raw pallas_call outside
apex_tpu/kernels/ (two import spellings), and registrations missing the
declared fallback."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import pallas_call          # flagged import

from apex_tpu.kernels.dispatch import register_kernel


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0


def model_path_kernel(x):
    # flagged: pallas_call wired straight into model code — no XLA
    # fallback seam
    return pl.pallas_call(
        _double_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


def aliased_spelling(x):
    return pallas_call(                                   # flagged call
        _double_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


# flagged: no xla_fallback declared
register_kernel("orphan_kernel", doc="no fallback")

# flagged: fallback declared but empty
register_kernel("hollow_kernel", xla_fallback="")
