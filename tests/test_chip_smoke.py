"""Guards against a run that appears to work off the chip.

``chip_smoke.py`` must never pass on the wrong device; the peak tables
must not price a device they do not list; the launcher must not start
several processes that would each claim every chip; and the compile
cache goes where the one rule says (apex_tpu/compile_cache.py).
"""
import json
import os
import subprocess
import sys
import types
import warnings

import pytest

import bench
from apex_tpu import compile_cache
from apex_tpu.parallel import auto, multiproc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _device(kind, platform="tpu"):
    return types.SimpleNamespace(device_kind=kind, platform=platform)


@pytest.mark.parametrize("args", [[], ["--four-chips"]],
                         ids=["one_chip", "four_chips"])
def test_chip_smoke_fails_without_a_tpu(args):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_bench_default_path_fails_without_a_tpu():
    """No images/sec/chip from a CPU: the throughput configs refuse."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--no-kernels"],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["value"] is None and "no TPU" in rec["error"]


def test_peak_tables_resolve_the_v5e_chip():
    # what the chip reports: jax.devices()[0].device_kind == "TPU v5 lite"
    peak, kind = bench.peak_tflops(_device("TPU v5 lite"))
    assert (peak, kind) == (197.0, "tpu v5 lite")
    spec = auto.chip_spec([_device("TPU v5 lite")])
    assert spec is auto.CHIPS["v5e"] and spec.peak_flops == 197.0e12


def test_peak_tables_raise_on_an_unknown_device():
    with pytest.raises(ValueError, match="TPU v9"):
        bench.peak_tflops(_device("TPU v9"))
    with pytest.raises(ValueError, match="TPU v9"):
        auto.chip_spec([_device("TPU v9")])
    # the CPU backend is known, and priced as what it is
    assert auto.chip_spec([_device("cpu", "cpu")]) is auto.CHIPS["cpu"]


def test_multiproc_refuses_several_processes_on_a_tpu_host(monkeypatch):
    monkeypatch.setattr(multiproc, "_probe_local_devices",
                        lambda: ("tpu", 4))
    monkeypatch.setattr(multiproc.subprocess, "Popen", lambda *a, **k:
                        pytest.fail("started a child on a TPU host"))
    for argv in (["train.py"], ["--nproc", "2", "train.py"]):
        monkeypatch.setattr(sys, "argv", ["multiproc", *argv])
        with pytest.raises(SystemExit) as e:
            multiproc.main()
        assert "one process drives every local chip" in str(e.value)


def test_compile_cache_sets_nothing_when_the_environment_names_one(
        monkeypatch, tmp_path):
    import jax
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable().directory == str(tmp_path)
    assert updates == []
    # unset: one fixed directory inside the checkout, the same each time
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".apex_tpu_cache", "xla")
    assert compile_cache.enable().directory == fixed
    assert compile_cache.enable().directory == fixed
    (key, value), = set(updates)        # the one setting the helper makes
    assert key.endswith("compilation_cache_dir") and value == fixed


def test_native_runtime_build_failure_is_reported_with_stderr(
        monkeypatch, tmp_path):
    from apex_tpu import runtime
    bad = tmp_path / "runtime.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(runtime, "_SRC", str(bad))
    monkeypatch.setattr(runtime, "cache_root", lambda: str(tmp_path))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert runtime._build_and_load() is None
    (w,) = caught
    assert "build failed" in str(w.message) and "error" in str(w.message)
    assert not [f for f in os.listdir(tmp_path / "native")
                if f.endswith(".partial")]
