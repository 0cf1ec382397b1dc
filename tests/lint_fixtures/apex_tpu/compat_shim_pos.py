"""COMPAT-SHIM positive (scoped: this file sits under a directory named
apex_tpu, so the rule treats it as package code)."""
import jax
from jax.experimental.shard_map import shard_map as legacy_sm   # BAD
from jax.sharding import PartitionSpec as P


def wrap(f, mesh):
    # BAD: package code spells this compat.shard_map
    return jax.shard_map(f, mesh=mesh, in_specs=P("dp"),
                         out_specs=P("dp"))


def world(axis):
    # BAD: package code spells this compat.axis_size
    return jax.lax.axis_size(axis)


del legacy_sm
