"""Input pipeline: overlapped host→device prefetch.

TPU analogue of the reference examples' ``data_prefetcher``
(examples/imagenet/main_amp.py:264-313): there, a side CUDA stream overlaps
the H2D copy + normalize of batch N+1 with the compute of batch N.  Here the
same overlap comes from a background thread doing the host byte-work (native
normalize/cast, csrc/runtime.cpp) and issuing ``jax.device_put`` — JAX
transfers are async, and the jitted step's dispatch is too, so compute and
transfer pipeline naturally; the thread keeps the *host* work (decode,
normalize, layout) off the training loop's critical path.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from ..observe import spans as _spans


class DataPrefetcher:
    """Wrap a batch iterable; yields device-resident (input, target) pairs
    one step ahead of consumption.

    ``loader`` yields (images, target) with images uint8 NHWC (the raw
    decode layout) or any float array.  uint8 NHWC input goes through the
    fused native normalize→NCHW path; ``half_dtype`` additionally casts to
    bf16/fp16 on host before transfer (halving H2D bytes).  Iteration
    protocol matches the reference: ``next()`` returns (None, None) at end.

    ``accum_steps=K`` delivers pre-stacked ``(K, B, ...)`` microbatch
    blocks for the fused accumulation step
    (``make_train_step(accum_steps=K, accum_stacked=True)``): K
    consecutive loader batches are normalized/cast individually, stacked
    on a new leading axis on the host, and transferred as one block — one
    ``device_put`` (and one step dispatch) per accumulation window instead
    of K.  The bounded queue keeps ``depth`` whole windows in flight, so
    block N+1's host byte-work and transfer overlap window N's compute
    exactly as with single batches.  A trailing partial window (loader
    exhausted mid-block) is dropped, like a ``drop_last`` loader — the
    step program's (K, B, ...) signature is static.

    ``depth`` is the double-buffering knob: ``Executor.drive`` picks 2
    (next window's transfer in flight under the current dispatch) or 1
    (serialized, the overlap-off arm) from the executor's ``h2d``
    overlap setting — see ``runtime/executor.py``.
    """

    def __init__(self, loader, mean=None, std=None, half_dtype=None,
                 device=None, depth: int = 2, threads: int = 0,
                 channels_last: bool = False, accum_steps: int = 1):
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        self.accum_steps = accum_steps
        self.loader = iter(loader)
        # channels_last: keep uint8 batches NHWC through the normalize
        # (for nn.to_channels_last models — the decode layout IS the
        # compute layout, no transpose anywhere on the input path)
        self.channels_last = channels_last
        self.mean = np.asarray(
            mean if mean is not None else [0.485, 0.456, 0.406], np.float32)
        self.std = np.asarray(
            std if std is not None else [0.229, 0.224, 0.225], np.float32)
        self.half_dtype = half_dtype
        self.device = device
        self.threads = threads
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._done = False
        self._worker = threading.Thread(target=self._run, daemon=True)
        self._worker.start()

    def _prepare(self, images):
        from . import (f32_to_bf16, normalize_u8_nhwc_to_f32_nchw,
                       normalize_u8_nhwc_to_f32_nhwc)
        images = np.asarray(images)
        if images.dtype == np.uint8 and images.ndim == 4:
            norm = (normalize_u8_nhwc_to_f32_nhwc if self.channels_last
                    else normalize_u8_nhwc_to_f32_nchw)
            images = norm(images, self.mean, self.std, self.threads)
        if self.half_dtype is not None:
            import jax.numpy as jnp
            if jnp.dtype(self.half_dtype) == jnp.bfloat16 and \
                    images.dtype == np.float32:
                images = f32_to_bf16(images, self.threads)
            else:
                import ml_dtypes  # noqa: F401  (dtype registry)
                images = images.astype(jnp.dtype(self.half_dtype))
        return images

    def _put(self, item) -> bool:
        """Bounded put that gives up when the consumer closed us (so an
        abandoned prefetcher never leaves the worker pinned on a full
        queue holding device buffers)."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self):
        import jax
        try:
            window = []
            for images, target in self.loader:
                if self._stop.is_set():
                    return
                from . import executor as _executor
                images = self._prepare(images)
                if self.accum_steps == 1:
                    target = np.asarray(target)
                    nbytes = (getattr(images, "nbytes", 0) +
                              getattr(target, "nbytes", 0))
                    t0 = time.perf_counter()
                    with _spans.span("h2d"):
                        images = jax.device_put(images, self.device)
                        target = jax.device_put(target, self.device)
                        jax.block_until_ready(target)
                    _executor.note_h2d(nbytes, time.perf_counter() - t0)
                    if not self._put((images, target)):
                        return
                    continue
                window.append((images, np.asarray(target)))
                if len(window) < self.accum_steps:
                    continue
                # host-side stack into the (K, B, ...) block the fused
                # accumulation step scans — one transfer per window
                block = np.stack([w[0] for w in window])
                tgt = np.stack([w[1] for w in window])
                window = []
                nbytes = block.nbytes + tgt.nbytes
                t0 = time.perf_counter()
                with _spans.span("h2d", accum_steps=self.accum_steps):
                    block = jax.device_put(block, self.device)
                    tgt = jax.device_put(tgt, self.device)
                    jax.block_until_ready(tgt)
                _executor.note_h2d(nbytes, time.perf_counter() - t0)
                if not self._put((block, tgt)):
                    return
            # a partial trailing window is dropped (drop_last semantics)
        except Exception as e:  # surface in the consumer thread
            self._put(e)
        self._put(None)

    def next(self):
        # exhausted stays exhausted: repeated next() keeps returning
        # (None, None) like the reference prefetcher, no deadlock
        if self._done:
            return None, None
        # how long the step loop waited for its batch; the worker's
        # ``h2d`` spans are on its own thread
        with _spans.span("data.wait"):
            item = self._q.get()
        if item is None:
            self._done = True
            return None, None
        if isinstance(item, Exception):
            self._done = True
            raise item
        return item

    def close(self):
        """Release the worker and any queued device batches (safe to call
        any time, including after partial consumption)."""
        self._stop.set()
        self._done = True
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._worker.join(timeout=5)

    def __del__(self):
        try:
            self._stop.set()
        except Exception:
            pass

    def __iter__(self):
        while True:
            inp, tgt = self.next()
            if inp is None:
                return
            yield inp, tgt
