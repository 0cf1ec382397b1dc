"""apex_tpu/compat.py: the one module that names jax's ``shard_map`` and
``axis_size``.

Everything in the package goes through it — the lint below enforces that
no apex_tpu source file calls ``jax.shard_map`` directly — and under the
installed jax it passes both straight through to the native entry points.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import compat
from apex_tpu import lint as tpu_lint

PKG_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "apex_tpu")


def _compat_findings():
    """One source of truth: the COMPAT-SHIM rule of the apex_tpu.lint
    engine (these tests used to be ad-hoc regex greps; they are now thin
    wrappers asserting the engine reports zero findings)."""
    return tpu_lint.run([PKG_ROOT], select=["COMPAT-SHIM"], baseline=None)


def test_lint_no_direct_jax_shard_map_references():
    """Every shard_map call site goes through apex_tpu.compat."""
    bad = [f for f in _compat_findings().active()
           if "shard_map" in f.message]
    assert not bad, (
        "direct jax.shard_map references (use apex_tpu.compat.shard_map): "
        + "\n".join(f.format() for f in bad))


def test_lint_no_direct_lax_axis_size_references():
    bad = [f for f in _compat_findings().active()
           if "axis_size" in f.message]
    assert not bad, (
        "direct lax.axis_size references (use apex_tpu.compat.axis_size): "
        + "\n".join(f.format() for f in bad))


def test_lint_walk_covers_auto_planner():
    """The engine must actually SCAN the parallelism planner
    (parallel/auto.py drives shard_map through the compat module; a
    lint that silently skipped it could not enforce the invariant
    there)."""
    files = {os.path.relpath(p, PKG_ROOT)
             for p in _compat_findings().files}
    assert os.path.join("parallel", "auto.py") in files
    assert os.path.join("runtime", "step_cache.py") in files


def test_auto_planner_uses_compat_shard_map():
    """parallel/auto.py's explicit-axis wrap must resolve shard_map via
    apex_tpu.compat (the source-level lint above catches `jax.shard_map`
    spellings; this pins the positive side — the shim import is present
    and the module carries no direct jax.experimental.shard_map use)."""
    path = os.path.join(PKG_ROOT, "parallel", "auto.py")
    with open(path) as f:
        text = "\n".join(line.split("#", 1)[0]
                         for line in f.read().splitlines())
    assert "compat" in text and "compat.shard_map" in text
    assert "jax.experimental.shard_map" not in text


def _mesh():
    return Mesh(np.array(jax.devices()), ("data",))


def test_compat_shard_map_runs_with_check_vma():
    """The keyword surface call sites use (``check_vma`` included)
    reaches jax.shard_map unchanged."""
    mesh = _mesh()
    n = len(jax.devices())

    def body(x):
        return jax.lax.psum(x, "data")

    fn = compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_vma=False)
    x = jnp.arange(n, dtype=jnp.float32)
    out = np.asarray(jax.jit(fn)(x))
    np.testing.assert_allclose(out, np.full((n,), x.sum()))


def test_compat_axis_size_inside_shard_map():
    mesh = _mesh()
    n = len(jax.devices())

    def body(x):
        return x * compat.axis_size("data")

    fn = compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_vma=False)
    out = np.asarray(jax.jit(fn)(jnp.ones((n,), jnp.float32)))
    np.testing.assert_allclose(out, np.full((n,), float(n)))
