"""Test harness config.

Tests run on the CPU backend with 8 virtual devices, so mesh/collective
code paths are exercised without an accelerator (SURVEY.md §4: the
reference tests distributed behavior single-node with
--nproc_per_node=2; our analogue is an 8-device virtual mesh).  The
environment variables below must be set before jax is imported anywhere.
What runs on the chip is ``chip_smoke.py`` through the chip tool, not
this suite (README.md, "Tests").
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()

from apex_tpu import compile_cache  # noqa: E402

import jax  # noqa: E402

# Persistent XLA compilation cache: the model/inference suites compile
# many 8-way shard_map programs, which dominates suite wall time.  The
# cache (keyed on the lowered HLO, so code changes invalidate naturally)
# makes repeat runs skip identical compiles; only compiles over 0.5s are
# stored to keep cold-run overhead negligible.  Its directory follows
# the package's one rule (apex_tpu/compile_cache.py).
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


@pytest.fixture
def reset_amp():
    """Clear global amp state (shared by the e2e and L1 suites)."""
    from apex_tpu.amp._amp_state import reset as _r
    _r()
    return _r
