"""The one module that names jax's ``shard_map`` and ``axis_size``.

Package code imports both from here (the COMPAT-SHIM lint rule and
``tests/test_compat.py`` keep it so); under the installed jax they are
the native entry points, passed through.
"""
from __future__ import annotations

import jax

shard_map = jax.shard_map
axis_size = jax.lax.axis_size
