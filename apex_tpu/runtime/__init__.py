"""apex_tpu.runtime — native host runtime (C++ data plane).

The reference keeps its host-side data plane in C++ (`apex_C`
flatten/unflatten, csrc/flatten_unflatten.cpp; the examples' side-stream
prefetcher byte-work, examples/imagenet/main_amp.py:264-302).  This package
is the TPU-native equivalent: a small C++ library (csrc/runtime.cpp) built
on first use with the system toolchain and bound over ctypes — no torch, no
pybind11.  Where the build fails it says so once, with the compiler's
stderr, and the numpy fallbacks take over, mirroring the reference's
Python-only install path (setup.py extensions optional,
README.md:130-139).

Public surface:
  flatten(arrays) / unflatten(flat, like)   — bucket coalescing (apex_C)
  normalize_u8_nhwc_to_f32_nchw(...)        — fused decode-side normalize
  f32_to_bf16(x)                            — bulk host cast (RNE)
  available()                               — True when the native lib loads
  DataPrefetcher                            — apex_tpu.runtime.data
  step_cache                                — compiled step-program cache
                                              (apex_tpu.runtime.step_cache)
  executor                                  — the one dispatch choke point:
                                              Program descriptors, donation
                                              policy, overlap knobs
                                              (apex_tpu.runtime.executor)
  resilience                                — atomic/async CheckpointManager,
                                              auto-resume, BadStepGuard
                                              (apex_tpu.runtime.resilience)
  chaos                                     — deterministic fault injection
                                              (apex_tpu.runtime.chaos)
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
import threading
import warnings

import numpy as np

from ..compile_cache import cache_root

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc", "runtime.cpp")
_lock = threading.Lock()
_lib = None


def _build_and_load():
    """Compile csrc/runtime.cpp into ``<cache_root>/native`` (named by
    the source's content hash, so an edited source rebuilds) and dlopen
    it.  A failure is reported once — ``_get`` caches the verdict — with
    the compiler's stderr, and the numpy fallbacks take over."""
    try:
        with open(_SRC, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError as e:
        warnings.warn(f"apex_tpu native runtime: cannot read {_SRC}: {e}; "
                      "using the numpy fallbacks")
        return None
    cache = os.path.join(cache_root(), "native")
    so = os.path.join(cache, f"libapex_runtime_{digest}.so")
    if not os.path.exists(so):
        os.makedirs(cache, exist_ok=True)
        # build beside the target, then rename: atomic vs concurrent
        # builders (two test processes racing on a cold checkout)
        fd, tmp = tempfile.mkstemp(suffix=".so.partial", dir=cache)
        os.close(fd)
        cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-std=c++17",
               _SRC, "-o", tmp]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        except (OSError, subprocess.SubprocessError) as e:
            os.unlink(tmp)
            stderr = getattr(e, "stderr", b"") or b""
            warnings.warn(
                f"apex_tpu native runtime: build failed ({e}); using the "
                f"numpy fallbacks.  Compiler stderr:\n"
                f"{stderr.decode(errors='replace')}")
            return None
        os.replace(tmp, so)
    try:
        return ctypes.CDLL(so)
    except OSError as e:
        warnings.warn(f"apex_tpu native runtime: cannot load {so}: {e}; "
                      "using the numpy fallbacks")
        return None


def _get():
    global _lib
    if _lib is None:
        with _lock:
            if _lib is None:
                lib = _build_and_load()
                if lib is not None:
                    lib.apex_flatten.argtypes = [
                        ctypes.POINTER(ctypes.c_void_p),
                        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                        ctypes.c_void_p, ctypes.c_int]
                    lib.apex_unflatten.argtypes = [
                        ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
                        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                        ctypes.c_int]
                    for nrm in ("apex_normalize_u8_nhwc_to_f32_nchw",
                                "apex_normalize_u8_nhwc_to_f32_nhwc"):
                        getattr(lib, nrm).argtypes = [
                            ctypes.c_void_p, ctypes.c_void_p,
                            ctypes.c_int64, ctypes.c_int64,
                            ctypes.c_int64, ctypes.c_int64,
                            ctypes.POINTER(ctypes.c_float),
                            ctypes.POINTER(ctypes.c_float), ctypes.c_int]
                    lib.apex_f32_to_bf16.argtypes = [
                        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                        ctypes.c_int]
                _lib = lib if lib is not None else False
    return _lib or None


def available() -> bool:
    """True when the native runtime library is (or can be) loaded."""
    return _get() is not None


def _as_contig(a):
    return np.ascontiguousarray(a)


def flatten(arrays, out=None, threads: int = 0):
    """Coalesce a list of same-dtype ndarrays into one flat 1-d array
    (apex_C.flatten, csrc/flatten_unflatten.cpp:5-8)."""
    arrays = [_as_contig(np.asarray(a)) for a in arrays]
    if not arrays:
        return np.empty((0,), np.float32)
    dtype = arrays[0].dtype
    if any(a.dtype != dtype for a in arrays):
        raise TypeError(
            "flatten: all arrays must share a dtype (bucket per dtype, "
            "reference split_half_float_double)")
    total = sum(a.size for a in arrays)
    if out is None:
        out = np.empty((total,), dtype)
    elif out.size != total or out.dtype != dtype:
        raise ValueError("flatten: bad out buffer")
    elif not out.flags["C_CONTIGUOUS"]:
        raise ValueError("flatten: out buffer must be C-contiguous")
    lib = _get()
    if lib is None:
        off = 0
        for a in arrays:
            out[off:off + a.size] = a.ravel()
            off += a.size
        return out
    n = len(arrays)
    srcs = (ctypes.c_void_p * n)(*[a.ctypes.data for a in arrays])
    nbytes = (ctypes.c_int64 * n)(*[a.nbytes for a in arrays])
    lib.apex_flatten(srcs, nbytes, n, out.ctypes.data, threads)
    return out


def unflatten(flat, like, threads: int = 0):
    """Split a flat array back into tensors shaped like ``like``
    (apex_C.unflatten, csrc/flatten_unflatten.cpp:10-13)."""
    flat = _as_contig(np.asarray(flat))
    outs = [np.empty(np.shape(t), flat.dtype) for t in like]
    total = sum(o.size for o in outs)
    if flat.size != total:
        raise ValueError(
            f"unflatten: flat has {flat.size} elements, targets need {total}")
    lib = _get()
    if lib is None:
        off = 0
        for o in outs:
            o[...] = flat[off:off + o.size].reshape(o.shape)
            off += o.size
        return outs
    n = len(outs)
    dsts = (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
    nbytes = (ctypes.c_int64 * n)(*[o.nbytes for o in outs])
    lib.apex_unflatten(flat.ctypes.data, dsts, nbytes, n, threads)
    return outs


def normalize_u8_nhwc_to_f32_nchw(batch, mean, std, threads: int = 0):
    """uint8 (N,H,W,C) → float32 (N,C,H,W), (x/255 - mean)/std fused — the
    prefetcher's per-batch byte work (main_amp.py:287-301) natively."""
    batch = _as_contig(np.asarray(batch, np.uint8))
    n, h, w, c = batch.shape
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    if mean.shape != (c,) or std.shape != (c,):
        raise ValueError(f"mean/std must have shape ({c},)")
    lib = _get()
    if lib is None:
        x = batch.astype(np.float32) / 255.0
        x = (x - mean) / std
        return np.ascontiguousarray(x.transpose(0, 3, 1, 2))
    out = np.empty((n, c, h, w), np.float32)
    lib.apex_normalize_u8_nhwc_to_f32_nchw(
        batch.ctypes.data, out.ctypes.data, n, h, w, c,
        mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), threads)
    return out


def normalize_u8_nhwc_to_f32_nhwc(batch, mean, std, threads: int = 0):
    """uint8 (N,H,W,C) → float32 (N,H,W,C), (x/255 - mean)/std fused,
    layout-preserving — the input path for channels-last models
    (nn.to_channels_last): the decode layout IS the compute layout, so
    the transpose disappears from the pipeline entirely."""
    batch = _as_contig(np.asarray(batch, np.uint8))
    n, h, w, c = batch.shape
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    if mean.shape != (c,) or std.shape != (c,):
        raise ValueError(f"mean/std must have shape ({c},)")
    lib = _get()
    if lib is None:
        x = batch.astype(np.float32) / 255.0
        return np.ascontiguousarray((x - mean) / std)
    out = np.empty((n, h, w, c), np.float32)
    lib.apex_normalize_u8_nhwc_to_f32_nhwc(
        batch.ctypes.data, out.ctypes.data, n, h, w, c,
        mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), threads)
    return out


def f32_to_bf16(x, threads: int = 0):
    """Bulk float32 → bfloat16 (round-to-nearest-even) on host."""
    import ml_dtypes
    x = _as_contig(np.asarray(x, np.float32))
    lib = _get()
    if lib is None:
        return x.astype(ml_dtypes.bfloat16)
    out = np.empty(x.shape, np.uint16)
    lib.apex_f32_to_bf16(x.ctypes.data, out.ctypes.data, x.size, threads)
    return out.view(ml_dtypes.bfloat16)


from .data import DataPrefetcher  # noqa: E402,F401
from . import step_cache  # noqa: E402,F401
from . import executor  # noqa: E402,F401
from .executor import (  # noqa: E402,F401
    Executor, Program, set_overlap, overlap_enabled)
from . import chaos  # noqa: E402,F401
from . import resilience  # noqa: E402,F401
from .resilience import (  # noqa: E402,F401
    BadStepGuard, CheckpointCorruptError, CheckpointManager,
    CheckpointReshardError, SaveHandle, TrainingDivergedError)
from . import elastic  # noqa: E402,F401
from .elastic import (  # noqa: E402,F401
    ElasticTrainer, current_devices, elastic_restore)

__all__ = ["flatten", "unflatten", "normalize_u8_nhwc_to_f32_nchw",
           "normalize_u8_nhwc_to_f32_nhwc", "f32_to_bf16", "available",
           "DataPrefetcher", "step_cache", "executor", "Executor",
           "Program", "set_overlap", "overlap_enabled", "chaos",
           "resilience",
           "CheckpointManager", "CheckpointCorruptError", "SaveHandle",
           "BadStepGuard", "TrainingDivergedError", "elastic",
           "CheckpointReshardError", "ElasticTrainer", "elastic_restore",
           "current_devices"]
