"""Parameter: a mutable handle on a jax.Array, the bridge between the
Apex-shaped stateful API (optimizers mutate ``p.data``, autograd fills
``p.grad``) and the functional JAX core.  Analogue of torch.nn.Parameter as
used throughout the reference optimizers/amp."""
from __future__ import annotations

import jax
import jax.numpy as jnp


class Parameter:
    __slots__ = ("data", "grad", "name", "requires_grad", "_derived")

    def __init__(self, data, name: str | None = None, requires_grad: bool = True):
        self.data = jnp.asarray(data)
        self.grad = None
        self.name = name
        self.requires_grad = requires_grad
        # reparameterization hook: when set, Ctx.value computes this
        # parameter from other parameters (e.g. WeightNorm g*v/||v||)
        # instead of reading .data (apex_tpu/reparameterization/)
        self._derived = None

    # -- array-ish surface -------------------------------------------------
    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def size(self):
        return self.data.size

    def numel(self) -> int:
        return int(self.data.size)

    def astype(self, dtype):
        return Parameter(self.data.astype(dtype), self.name, self.requires_grad)

    def half(self):
        return self.astype(jnp.float16)

    def bfloat16(self):
        return self.astype(jnp.bfloat16)

    def float(self):
        return self.astype(jnp.float32)

    def clone(self):
        p = Parameter(self.data, self.name, self.requires_grad)
        p.grad = self.grad
        return p

    def __array__(self, dtype=None):
        import numpy as np
        return np.asarray(self.data, dtype)

    def __jax_array__(self):
        return self.data

    def __repr__(self):
        return (f"Parameter(name={self.name!r}, shape={tuple(self.shape)}, "
                f"dtype={jnp.dtype(self.dtype).name})")


def is_param(x) -> bool:
    return isinstance(x, Parameter)


def param_values(params) -> list[jax.Array]:
    return [p.data for p in params]


def param_grads(params) -> list:
    return [p.grad for p in params]


def abstract_parameter(shape, dtype, name: str | None = None) -> Parameter:
    """A parameter with a shape and a type and no value yet
    (``data`` is a ``jax.ShapeDtypeStruct``): for a model whose weights
    are loaded or published after it is built, and too large to draw
    first.  Nothing can be computed from it until ``data`` is set."""
    p = Parameter(jnp.zeros((), dtype), name)
    p.data = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
    return p
