"""Headline benchmark: ImageNet ResNet-50, amp-O2-equivalent fused train step,
images/sec on one chip (BASELINE.md config 2; measurement method mirrors the
reference examples/imagenet/main_amp.py:390-397 — world_size*batch/avg_step_time).

Prints ONE JSON line to stdout:
  {"metric", "value", "unit", "vs_baseline", "step_time_ms", "tflops", "mfu",
   "compile_s", "kernels": {...}}
vs_baseline is measured against 800 img/s/chip — the commonly reported V100
Apex-O2 ResNet-50 number (the reference repo publishes no figure, BASELINE.md).

Failure behavior: every phase is stage-logged to stderr with elapsed time;
the device-measuring paths fail when JAX finds no TPU (they never fall back
to the CPU); compile falls back to smaller batches; a watchdog guarantees a
diagnostic JSON line naming the last-reached stage is emitted even on a
hang — never a bare traceback.
"""
import argparse
import contextlib
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

T0 = time.perf_counter()
STAGE = {"name": "import", "detail": ""}
V100_APEX_O2_IMGS_PER_SEC = 800.0

# vs_baseline anchor for the non-ResNet training configs (BERT/GPT/
# Llama/seq2seq/ViT/DCGAN), where no like-for-like measured V100+Apex
# number exists (the reference publishes none, BASELINE.md).  The
# anchor is DERIVED, with the arithmetic in the emitted line:
# the throughput a V100 would deliver on the same step at 30% MFU of
# its 125 TFLOP/s fp16 tensor-core peak (0.3 is the V100-era rule of
# thumb for well-tuned fp16 transformer/conv training).  anchor
# items/s = 37.5e12 / (step FLOPs / batch), so
# vs_baseline = achieved TFLOP/s / 37.5 — self-contained and coarse by
# construction, but it makes every bench line adjudicable.
V100_EST_SUSTAINED_TFLOPS = 0.30 * 125.0

# bf16 peak TFLOP/s by TPU generation (public spec sheets); used for MFU
_PEAK_TFLOPS = (
    ("v6", 918.0), ("v5p", 459.0), ("v5e", 197.0), ("v5 lite", 197.0),
    ("v4", 275.0), ("v3", 123.0), ("v2", 46.0),
)


def log(msg):
    print(f"[bench +{time.perf_counter() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def stage(name, detail=""):
    STAGE["name"], STAGE["detail"] = name, detail
    log(f"stage: {name}" + (f" ({detail})" if detail else ""))


def emit(obj):
    print(json.dumps(obj), flush=True)


FAIL_METRIC = {"metric": "resnet50_imagenet_images_per_sec_per_chip_ampO2",
               "unit": "images/sec/chip"}


def fail(error, **extra):
    out = {"metric": FAIL_METRIC["metric"],
           "value": None, "unit": FAIL_METRIC["unit"], "vs_baseline": None,
           "error": error, "stage": STAGE["name"],
           "stage_detail": STAGE["detail"],
           "elapsed_s": round(time.perf_counter() - T0, 1)}
    out.update(extra)
    emit(out)


def start_watchdog(budget_s):
    """Emit a diagnostic JSON and hard-exit if the bench hangs."""
    def _fire():
        fail("watchdog_timeout", budget_s=budget_s)
        os._exit(3)
    t = threading.Timer(budget_s, _fire)
    t.daemon = True
    t.start()
    return t


#: carried IN the emitted JSON line of every backend_unresponsive exit,
#: so the record stays parseable and says what happened
UNRESPONSIVE_HINT = ("backend did not respond: a chip belongs to one "
                     "process at a time — check that no other process "
                     "holds it, then rerun")


def _run_with_timeout(fn, timeout_s, msg):
    """Run ``fn`` in a daemon thread; return its value, re-raise its
    exception, or — when it has not finished after ``timeout_s`` — emit
    the named diagnostic JSON with the hint and hard-exit (a backend
    call that hangs cannot be interrupted from Python)."""
    done = {}

    def _target():
        try:
            done["val"] = fn()
        except Exception as e:      # noqa: BLE001 — re-raised below
            done["err"] = e

    t = threading.Thread(target=_target, daemon=True)
    t.start()
    t.join(timeout_s)
    if "err" in done:
        raise done["err"]
    if "val" in done:
        return done["val"]
    fail(msg, hint=UNRESPONSIVE_HINT)
    os._exit(4)


def init_backend(probe_timeout_s=75):
    """The devices of the device-measuring paths: a TPU or an error.
    A number measured on the CPU is never printed under a per-chip
    metric's name, so there is no platform override."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import compile_cache
    compile_cache.enable()

    ds = _run_with_timeout(
        jax.devices, probe_timeout_s,
        "backend_unresponsive: jax.devices() did not complete within "
        f"{probe_timeout_s}s")
    log(f"backend up: {len(ds)}x {ds[0].device_kind or ds[0].platform}")
    if ds[0].platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX found {len(ds)}x {ds[0].platform}; the "
            f"throughput configs measure a chip and do not run without "
            f"one")

    # prove the backend computes before spending the watchdog budget on
    # a model compile
    stage("backend_probe", f"{probe_timeout_s}s limit")

    def _probe():
        x = jnp.ones((128, 128))
        return float(jnp.sum(x @ x))

    val = _run_with_timeout(
        _probe, probe_timeout_s,
        "backend_unresponsive: device listing works but a trivial "
        f"compute did not complete within {probe_timeout_s}s")
    log(f"backend probe ok ({val:.0f})")
    return ds


def peak_tflops(device):
    """(bf16 peak TFLOP/s, lower-cased device_kind).  A device the table
    does not list is an error, not an MFU of ``None``."""
    kind = (device.device_kind or "").lower()
    for key, val in _PEAK_TFLOPS:
        if key in kind:
            return val, kind
    raise ValueError(f"peak_tflops: no entry for device_kind "
                     f"{device.device_kind!r} — add it to _PEAK_TFLOPS")


def resnet50_step_flops(batch):
    """Analytic fallback: ResNet-50 fwd ≈ 4.09 GFLOP/img @224 (2*MACs);
    training step ≈ 3x forward (fwd + 2x in bwd)."""
    return 3 * 4.089e9 * batch


def flash_attn_step_flops(attn_shapes):
    """Model FLOPs of the attention-score matmuls for one fwd+bwd step.

    XLA cost analysis cannot see inside Pallas custom calls, so when the
    flash kernel carries the attention a step's reported FLOPs are missing
    the QK^T and PV matmuls entirely — the reported MFU is a floor
    (VERDICT round 2 missing #2).  This is the analytic complement, the
    same counting as pyprof's `_attention_family` model
    (pyprof/prof/models.py): per (layers, b, h, sq, sk, d, causal) entry,
    fwd = 2 matmuls = 4·area·d FLOPs with area = b·h·sq·sk (halved for
    causal), bwd = 2× fwd.  MFU convention counts MODEL FLOPs, so the
    flash backward's in-kernel recompute is deliberately NOT counted.
    Softmax (≈5·area) and the Pallas LayerNorm (O(b·s·e)) are noise at
    these shapes and left out.
    """
    from apex_tpu.kernels.attention import _compiled_takes

    total = 0.0
    for layers, b, h, sq, sk, d, causal in attn_shapes:
        if not _compiled_takes(b, h, sq, sk):
            # the flash rule sends this shape to the XLA path, whose
            # matmuls cost analysis already counts — adding the
            # complement would double-count
            continue
        area = b * h * sq * sk * (0.5 if causal else 1.0)
        total += layers * 12.0 * area * d
    return total


def _rel_err(a, b):
    import jax.numpy as jnp
    denom = float(jnp.max(jnp.abs(b))) + 1e-6
    return float(jnp.max(jnp.abs(a - b))) / denom


@contextlib.contextmanager
def _pin_flash_dispatch():
    """Force the flash kernel at every shape for the duration (the
    kernel parity/timing paths must exercise the KERNEL, not whatever
    the shape-aware dispatch would pick), restoring the production
    dispatch afterwards — bench must not leave a process-global
    override behind."""
    from apex_tpu.kernels import attention as kattn
    prev = kattn.FLASH_MIN_SK
    kattn.FLASH_MIN_SK = 0
    try:
        yield
    finally:
        kattn.FLASH_MIN_SK = prev


def run_kernel_checks():
    """Run the L0 Pallas kernel numerics checks with the kernels actually
    compiled for the attached backend (VERDICT round 1: kernels had only ever
    run in interpret mode on CPU).  Pallas-compiled vs jnp-fallback parity +
    VMEM-fit guard for the attention block sizes."""
    import jax
    import numpy as np

    mode = "compiled" if jax.default_backend() == "tpu" else "interpret"
    # the parity check must exercise the KERNEL at every shape — pin the
    # shape-aware dispatch open (it would route small S to XLA and this
    # would silently compare XLA to itself); _pin_flash_dispatch restores
    # the production dispatch afterwards
    with _pin_flash_dispatch():
        return _run_kernel_checks_inner(mode, {"mode": mode},
                                        np.random.default_rng(0))


def _run_kernel_checks_inner(mode, results, rng):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.ops import pallas as pal
    from apex_tpu.ops.pallas.attention import vmem_fit

    # Pin matmuls to f32-exact (6-pass) so the comparison isolates kernel
    # correctness from MXU bf16 rounding: under default precision the Pallas
    # and jnp paths each do bf16-blocked matmuls with different blockings and
    # legitimately disagree at ~1e-3.  Production runs keep default (fast)
    # precision; this context only governs the parity check.
    def prec():
        return jax.default_matmul_precision("highest")

    # --- fused layer norm fwd + bwd ---
    try:
        from apex_tpu.normalization import fused_layer_norm_affine
        x = jnp.asarray(rng.standard_normal((256, 512)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((512,)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((512,)), jnp.float32)

        def loss(x, w, b):
            return jnp.sum(fused_layer_norm_affine(x, w, b, (512,)) ** 2)

        with prec(), pal.force_mode(mode):
            out_k = fused_layer_norm_affine(x, w, b, (512,))
            g_k = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
        with prec(), pal.force_mode("off"):
            out_r = fused_layer_norm_affine(x, w, b, (512,))
            g_r = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
        err = max(_rel_err(out_k, out_r),
                  *[_rel_err(a, b) for a, b in zip(g_k, g_r)])
        results["layer_norm"] = ("pass" if err < 1e-4
                                 else f"fail: rel_err={err:.2e}")
        results["layer_norm_rel_err"] = err
    except Exception as e:
        results["layer_norm"] = f"error: {type(e).__name__}: {e}"

    # --- fused rms norm fwd + bwd (the Llama-family norm) ---
    try:
        from apex_tpu.normalization import fused_rms_norm_affine
        x = jnp.asarray(rng.standard_normal((256, 512)), jnp.float32)
        w = jnp.asarray(1 + 0.1 * rng.standard_normal((512,)), jnp.float32)

        def rloss(x, w):
            return jnp.sum(fused_rms_norm_affine(x, w, (512,)) ** 2)

        with prec(), pal.force_mode(mode):
            out_k = fused_rms_norm_affine(x, w, (512,))
            g_k = jax.grad(rloss, argnums=(0, 1))(x, w)
        with prec(), pal.force_mode("off"):
            out_r = fused_rms_norm_affine(x, w, (512,))
            g_r = jax.grad(rloss, argnums=(0, 1))(x, w)
        err = max(_rel_err(out_k, out_r),
                  *[_rel_err(a, b) for a, b in zip(g_k, g_r)])
        results["rms_norm"] = ("pass" if err < 1e-4
                               else f"fail: rel_err={err:.2e}")
        results["rms_norm_rel_err"] = err
    except Exception as e:
        results["rms_norm"] = f"error: {type(e).__name__}: {e}"

    # --- flash attention fwd + bwd ---
    try:
        from apex_tpu.contrib.multihead_attn.attn_funcs import flash_attention
        q = jnp.asarray(rng.standard_normal((2, 4, 256, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 4, 256, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 4, 256, 64)), jnp.float32)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        with prec(), pal.force_mode(mode):
            out_k = flash_attention(q, k, v, causal=True)
            g_k = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        with prec(), pal.force_mode("off"):
            out_r = flash_attention(q, k, v, causal=True)
            g_r = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        err = max(_rel_err(out_k, out_r),
                  *[_rel_err(a, b) for a, b in zip(g_k, g_r)])
        results["attention"] = ("pass" if err < 1e-4
                                else f"fail: rel_err={err:.2e}")
        results["attention_rel_err"] = err
    except Exception as e:
        results["attention"] = f"error: {type(e).__name__}: {e}"

    # --- fused xentropy fwd + bwd (the LM loss kernel) ---
    try:
        from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
        lg = jnp.asarray(rng.standard_normal((64, 300)), jnp.float32)
        lab = jnp.asarray(rng.integers(0, 300, (64,)))

        def xloss(lg):
            return jnp.sum(softmax_cross_entropy_loss(
                lg, lab, 0.1, -1, True) ** 2)

        # the kernel is opt-in on-chip (it loses the perf A/B); the
        # PARITY check must still exercise it, not compare the jnp
        # path to itself
        prev = os.environ.get("APEX_TPU_XENT_KERNEL")
        os.environ["APEX_TPU_XENT_KERNEL"] = "1"
        try:
            with prec(), pal.force_mode(mode):
                out_k = softmax_cross_entropy_loss(lg, lab, 0.1, -1, True)
                g_k = jax.grad(xloss)(lg)
        finally:
            if prev is None:
                os.environ.pop("APEX_TPU_XENT_KERNEL", None)
            else:
                os.environ["APEX_TPU_XENT_KERNEL"] = prev
        with prec(), pal.force_mode("off"):
            out_r = softmax_cross_entropy_loss(lg, lab, 0.1, -1, True)
            g_r = jax.grad(xloss)(lg)
        err = max(_rel_err(out_k, out_r), _rel_err(g_k, g_r))
        results["xentropy"] = ("pass" if err < 1e-4
                               else f"fail: rel_err={err:.2e}")
        results["xentropy_rel_err"] = err
    except Exception as e:
        results["xentropy"] = f"error: {type(e).__name__}: {e}"

    # --- VMEM-fit guard across representative shapes ---
    vmem = {}
    for sq, d in [(256, 64), (2048, 128), (8192, 256), (4096, 1024)]:
        r = vmem_fit(sq, sq, d)
        vmem[f"S{sq}_D{d}"] = ("fits" if r["fits"] else "OVER") + \
            f" bq={r['bq']} bk={r['bk']} {r['est_bytes'] // 1024}KiB"
        if not r["fits"]:
            results["vmem_guard"] = "fail"
    results.setdefault("vmem_guard", "pass")
    results["vmem"] = vmem
    return results


def run_profile(kind, batch, seq_len, top_n=15, plain_loss=False,
                nhwc=False,
                remat=False, size="small", loss_mode=None):
    """Measured per-op-family attribution of one train step — the
    diagnosis tool behind the MFU numbers (VERDICT r2 weak #2: ResNet
    MFU saturates by batch 128 'suggesting layout or input-path
    overhead'; this run names the ops that carry the time).  Uses the
    pyprof measured pipeline (jax.profiler trace joined to annotate
    scopes through HLO metadata, pyprof/parse/trace.py) and aggregates
    measured thunk time by op family.

    Meaningful on TPU, where the device lanes carry one event per
    HLO-named fusion; the CPU runtime collapses a large donated step
    into opaque copy/call thunks, so off-chip runs may report most time
    as unattributed (the JSON still carries the split honestly).
    """
    from apex_tpu.pyprof.parse.trace import profile_step

    lm = loss_mode or ("plain" if plain_loss else "chunked")
    if kind == "bert":
        step, arrays, _, _ = build_bert_step(batch, seq_len, plain_loss)
    elif kind == "gpt":
        step, arrays, _, _ = build_gpt_step(batch, seq_len, remat=remat,
                                            size=size, loss_mode=lm)
    elif kind == "llama":
        step, arrays, _, _ = build_llama_step(batch, seq_len,
                                              remat=remat, loss_mode=lm)
    elif kind == "vit":
        step, arrays, _, _ = build_vit_step(batch)
    else:
        step, arrays, _, _ = build_resnet_step(batch, nhwc=nhwc)

    stage("profile", f"{kind} batch={batch}")
    rows, report = profile_step(step._raw_step_fn, step.state, *arrays)
    agg = {}
    for r in rows:
        if r.get("dur_us") is None:
            continue
        key = (r["op"], r.get("dir", "fwd"))
        agg[key] = agg.get(key, 0.0) + float(r["dur_us"])
    total = sum(agg.values())
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:top_n]
    # rows carry PER-EXECUTION durations (merge_measurements divides by
    # executions); the report's unattributed sum spans all executions —
    # normalize so the matched/unattributed split shares one scale
    n_exec = max(1, int(report.get("executions", 1)))
    return {
        "kind": kind, "batch": batch,
        "matched_us": round(total, 1),
        "unattributed_us": round(
            float(report.get("unattributed_us", 0.0)) / n_exec, 1),
        # per-category split of the unmatched bucket (same per-execution
        # scale): names whether unattributed time is layout transposes,
        # copies, or unannotated fusions
        "unattributed_top": {
            k: round(v / n_exec, 1)
            for k, v in sorted(report.get("unattributed_by", {}).items(),
                               key=lambda kv: -kv[1])[:10]},
        "top_ops": [
            {"op": op, "dir": d, "us": round(us, 1),
             "pct": round(100.0 * us / total, 1) if total else None}
            for (op, d), us in top],
    }


def run_kernel_timing(iters=30, reps=5):
    """A/B-time the Pallas kernels against their plain-XLA (jnp fallback)
    lowerings on the attached backend: fwd+bwd step time per shape, with
    the speedup the fused kernel delivers.  This is the TPU analogue of
    the reference justifying its fused CUDA kernels by beating the unfused
    path (apex/contrib/multihead_attn/README.md:6-14) — if a Pallas kernel
    does not beat XLA's own fusion on a shape, that shows up here as
    speedup < 1.  Only meaningful when mode == 'compiled' (real TPU);
    elsewhere the jnp path runs in both arms and the numbers are noise.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.ops import pallas as pal
    from apex_tpu.contrib.multihead_attn.attn_funcs import flash_attention
    from apex_tpu.normalization import fused_layer_norm_affine

    on_tpu = jax.default_backend() == "tpu"
    # off-TPU there is nothing honest to time: interpret mode is a Python
    # emulation (1000x off), and a fallback-vs-fallback "A/B" is noise —
    # return immediately rather than burn minutes on meaningless arms
    if not on_tpu:
        log("kernel timing skipped: no TPU backend")
        return {"mode": "skipped (no TPU)",
                "layer_norm": {}, "attention": {}}, None
    from apex_tpu.normalization import fused_rms_norm_affine

    mode = "compiled"
    results = {"mode": mode, "layer_norm": {}, "rms_norm": {},
               "attention": {}, "xentropy": {}, "lm_head_xent": {}}
    rng = np.random.default_rng(0)

    _sync = jax.block_until_ready

    def _segment(fn, args):
        """One timed segment of ``iters`` calls, closed by
        block_until_ready."""
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        _sync(out)
        return (time.perf_counter() - t0) / iters

    def _ab(build_fn, args, label, bucket):
        """Variance-controlled A/B (VERDICT r4 #3): both arms compile
        first, then ``reps`` timed segments run INTERLEAVED
        (pallas/xla/pallas/xla/...), so drift — clock ramps,
        background activity — lands on both arms equally.
        Reported per arm: median segment time and IQR; the speedup is
        the ratio of medians.  Round-4's single-run sequential arms are
        the method this replaces (LN bf16 swung 0.995-1.73x across
        sessions under it)."""
        row = {"reps": reps, "iters": iters}
        fns = {}
        for arm, m in (("pallas", mode), ("xla", "off")):
            stage("kernel_timing", f"{bucket} {label} {arm} compile")
            with pal.force_mode(m):
                try:
                    fn = build_fn()
                    _sync(fn(*args))   # compile + warm inside the mode ctx
                    # jit dispatch captured the forced mode at trace
                    # time, so the compiled fn keeps its arm outside
                    # the context
                    fns[arm] = fn
                except Exception as e:
                    row[f"{arm}_ms"] = None
                    row[f"{arm}_error"] = f"{type(e).__name__}: {e}"
        seg = {arm: [] for arm in fns}
        for rep in range(reps):
            stage("kernel_timing", f"{bucket} {label} rep {rep + 1}/{reps}")
            for arm, fn in fns.items():
                seg[arm].append(_segment(fn, args))
        for arm, ts in seg.items():
            ts = sorted(ts)
            n_ = len(ts)
            med = ts[n_ // 2] if n_ % 2 else (ts[n_ // 2 - 1]
                                              + ts[n_ // 2]) / 2
            q1, q3 = ts[n_ // 4], ts[(3 * n_) // 4]
            row[f"{arm}_ms"] = round(med * 1e3, 4)
            row[f"{arm}_iqr_ms"] = round((q3 - q1) * 1e3, 4)
        if row.get("pallas_ms") and row.get("xla_ms"):
            row["speedup"] = round(row["xla_ms"] / row["pallas_ms"], 3)
        results[bucket][label] = row
        log(f"kernel timing {bucket} {label}: {row}")
        # one JSON line per completed row, immediately: a later shape's
        # hang must not lose the rows already measured
        emit({"metric": "pallas_kernel_ab", "kernel": bucket,
              "shape": label, **row})

    # --- fused layer norm, training shapes (tokens x hidden), fwd+bwd ---
    for (n, e), dtype in [((8192, 768), jnp.float32),
                          ((16384, 1024), jnp.float32),
                          ((16384, 1024), jnp.bfloat16)]:
        x = jnp.asarray(rng.standard_normal((n, e)), dtype)
        w = jnp.ones((e,), jnp.float32)
        b = jnp.zeros((e,), jnp.float32)

        def build(e=e):
            def loss(x, w, b):
                out = fused_layer_norm_affine(x, w, b, (e,))
                return jnp.sum(out.astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        _ab(build, (x, w, b), f"N{n}_E{e}_{jnp.dtype(dtype).name}",
            "layer_norm")

    # --- fused rms norm (the Llama-family norm), same shapes ---
    for (n, e), dtype in [((8192, 768), jnp.float32),
                          ((16384, 1024), jnp.bfloat16)]:
        x = jnp.asarray(rng.standard_normal((n, e)), dtype)
        w = jnp.ones((e,), jnp.float32)

        def build(e=e):
            def loss(x, w):
                out = fused_rms_norm_affine(x, w, (e,))
                return jnp.sum(out.astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1)))
        _ab(build, (x, w), f"N{n}_E{e}_{jnp.dtype(dtype).name}",
            "rms_norm")

    # --- flash attention, VMEM-guard shapes, fwd+bwd ---
    for b_, h, s, d, causal, dtype in [
            (8, 12, 256, 64, True, jnp.bfloat16),
            # S=512 sits exactly on the shape-aware dispatch threshold
            # (attn_funcs: keys < 512 -> XLA): this row decides whether
            # the boundary is placed right now that causal block-skip
            # landed
            (8, 12, 512, 64, True, jnp.bfloat16),
            (4, 12, 1024, 64, True, jnp.bfloat16),
            (1, 8, 2048, 128, True, jnp.bfloat16),
            (4, 12, 1024, 64, False, jnp.bfloat16)]:
        q = jnp.asarray(rng.standard_normal((b_, h, s, d)), dtype)
        k = jnp.asarray(rng.standard_normal((b_, h, s, d)), dtype)
        v = jnp.asarray(rng.standard_normal((b_, h, s, d)), dtype)

        def build(causal=causal):
            def loss(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, causal=causal)
                    .astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        _ab(build, (q, k, v),
            f"B{b_}_H{h}_S{s}_D{d}{'_causal' if causal else ''}"
            f"_{jnp.dtype(dtype).name}", "attention")

    # --- banded (Mistral sliding-window) attention: the kernel skips
    # fully-out-of-band blocks, so the claim to verify is O(S*window)
    # vs the XLA arm's O(S^2) materialized banded scores ---
    for b_, h, s, d, w, dtype in [(4, 12, 2048, 64, 256, jnp.bfloat16)]:
        q = jnp.asarray(rng.standard_normal((b_, h, s, d)), dtype)
        k = jnp.asarray(rng.standard_normal((b_, h, s, d)), dtype)
        v = jnp.asarray(rng.standard_normal((b_, h, s, d)), dtype)

        def build(w=w):
            def loss(q, k, v):
                return jnp.sum(
                    flash_attention(q, k, v, causal=True,
                                    sliding_window=w)
                    .astype(jnp.float32) ** 2)
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        _ab(build, (q, k, v),
            f"B{b_}_H{h}_S{s}_D{d}_w{w}_{jnp.dtype(dtype).name}",
            "attention")

    # --- fused xentropy at the LM loss shapes: the jnp arm's f32
    # casts of (rows, vocab) materialize (~14 ms/step measured on the
    # GPT-128 profile); the kernel casts block-locally in VMEM ---
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    _prev_xk = os.environ.get("APEX_TPU_XENT_KERNEL")
    os.environ["APEX_TPU_XENT_KERNEL"] = "1"    # the kernel is opt-in
    try:
        for rows, c in [(8192, 50257), (16384, 50257)]:
            logits = jnp.asarray(rng.standard_normal((rows, c)),
                                 jnp.bfloat16)
            labels = jnp.asarray(rng.integers(0, c, (rows,)))

            def build():
                def loss(lg):
                    return jnp.mean(softmax_cross_entropy_loss(
                        lg, labels, 0.0, -1, True))
                return jax.jit(jax.grad(loss))
            _ab(build, (logits,), f"R{rows}_V{c}_bfloat16", "xentropy")
    finally:
        if _prev_xk is None:
            os.environ.pop("APEX_TPU_XENT_KERNEL", None)
        else:
            os.environ["APEX_TPU_XENT_KERNEL"] = _prev_xk

    # --- EXPERIMENTAL fused lm-head + loss (logits never in HBM):
    # not wired into any model — this row decides whether it gets wired.
    # jnp arm = the production chain (head matmul + fused xentropy).
    from apex_tpu.ops.pallas.lm_head_xent import fused_lm_head_xent
    for rows, vcb, e_ in [(8192, 50257, 768)]:
        x_ = jnp.asarray(rng.standard_normal((rows, e_)) * 0.3,
                         jnp.bfloat16)
        emb_ = jnp.asarray(rng.standard_normal((vcb, e_)) * 0.1,
                           jnp.bfloat16)
        lab_ = jnp.asarray(rng.integers(0, vcb, (rows,)))

        def build():
            # the op dispatches internally: kernel under pallas modes,
            # the matmul + log-softmax chain otherwise (the 'off' arm)
            def loss(x, emb):
                return jnp.mean(fused_lm_head_xent(x, emb, lab_))
            return jax.jit(jax.grad(loss, argnums=(0, 1)))
        _ab(build, (x_, emb_), f"R{rows}_V{vcb}_E{e_}_bfloat16",
            "lm_head_xent")

    # --- MLP: fused whole-chain step vs per-op eager dispatch at the
    # reference's exact test shapes (tests/L0/run_mlp/test_mlp.py:
    # batch 1024, sizes 480-1024-1024-512-256-1).  The reference built
    # mlp_cuda purely to fuse Linear+bias+ReLU chains that eager torch
    # dispatches op-by-op; the TPU analogue of "unfused" is eager jax
    # (one dispatch per primitive), of "fused" one jitted fwd+bwd.
    # Not a Pallas kernel — reported as its own row, outside the
    # shipping-kernel gmean.
    from apex_tpu.mlp import MLP
    import apex_tpu.nn as nn_
    nn_.manual_seed(0)
    mlp = MLP([480, 1024, 1024, 512, 256, 1])
    mlp_vals = [p.data.astype(jnp.bfloat16) for p in mlp.parameters()]
    mlp_plist = list(mlp.parameters())
    xin = jnp.asarray(rng.standard_normal((1024, 480)), jnp.bfloat16)

    def mlp_loss(x, vals):
        from apex_tpu.nn.modules import Ctx
        env = {id(p): v for p, v in zip(mlp_plist, vals)}
        ctx = Ctx(env=env, stats_out={}, training=True, key=None)
        return jnp.sum(mlp.forward(ctx, x).astype(jnp.float32) ** 2)

    mlp_grad = jax.grad(mlp_loss, argnums=(0, 1))
    mlp_jit = jax.jit(mlp_grad)
    row = {"reps": reps, "iters": iters}
    seg = {"fused": [], "unfused": []}
    _sync(mlp_jit(xin, mlp_vals))
    _sync(mlp_grad(xin, mlp_vals))
    for rep in range(reps):
        stage("kernel_timing", f"mlp rep {rep + 1}/{reps}")
        for arm, fn in (("fused", mlp_jit), ("unfused", mlp_grad)):
            t0 = time.perf_counter()
            for _ in range(max(1, iters // (1 if arm == "fused" else 3))):
                out = fn(xin, mlp_vals)
            _sync(out)
            n_it = max(1, iters // (1 if arm == "fused" else 3))
            seg[arm].append((time.perf_counter() - t0) / n_it)
    for arm, ts in seg.items():
        ts = sorted(ts)
        n_ = len(ts)
        med = ts[n_ // 2] if n_ % 2 else (ts[n_ // 2 - 1]
                                          + ts[n_ // 2]) / 2
        row[f"{arm}_ms"] = round(med * 1e3, 4)
        row[f"{arm}_iqr_ms"] = round(
            (ts[(3 * n_) // 4] - ts[n_ // 4]) * 1e3, 4)
    row["speedup"] = round(row["unfused_ms"] / row["fused_ms"], 3)
    results["mlp"] = {"B1024_480-1024-1024-512-256-1_bfloat16": row}
    log(f"kernel timing mlp: {row}")
    emit({"metric": "mlp_fused_vs_unfused_ab",
          "shape": "B1024_480-1024-1024-512-256-1_bfloat16", **row})

    # THE gmean definition (one, emitted here — VERDICT r4 weak #3 had
    # three competing values in flight): geometric mean of the
    # median-of-reps speedups over the SHIPPING kernels' rows — the
    # layer_norm / rms_norm / attention buckets, whose kernels
    # production dispatch actually engages.  The xentropy and
    # lm_head_xent buckets are measured and reported above as evidence
    # but excluded: standalone xentropy is gated off (it loses), and
    # lm_head_xent ships via the chunked-loss path, not this kernel.
    ups = [r["speedup"]
           for bkt in ("layer_norm", "rms_norm", "attention")
           for r in results[bkt].values() if r.get("speedup")]
    gmean = float(np.exp(np.mean(np.log(ups)))) if ups else None
    results["gmean_definition"] = (
        "geomean of median-of-reps speedups, shipping kernels only "
        "(layer_norm+rms_norm+attention buckets)")
    return results, gmean


def time_compiled_step(step, batch_arrays, iters, warmup, analytic_flops,
                       pallas_attn_flops=0.0, scanned_hot_loop=False):
    """Compile + time a fused train step: returns (dt, compile_s, flops,
    flops_source).  FLOPs come from XLA cost analysis with
    ``analytic_flops()`` as the fallback; ``pallas_attn_flops`` is the
    analytic attention-matmul complement added on top of cost analysis
    when the compiled program actually contains Pallas custom calls
    (cost analysis reports 0 FLOPs for them, so without the complement
    flash-attention configs understate MFU).  Timed windows close with
    ``jax.block_until_ready`` on the step's state (chip_smoke.py's device
    phase checks that it waits for the device)."""
    import jax

    tc = time.perf_counter()
    fn = step._step_fn
    if not hasattr(fn, "lower"):
        # executor-routed steps hold a submit closure, not the jitted
        # fn: AOT-compile the raw step under the same donation the
        # executor's program carries, so the timed executable matches
        # what step() dispatches
        fn = jax.jit(step._raw_step_fn,
                     donate_argnums=(0,)
                     if getattr(step, "_donate_state", False) else ())
    compiled = fn.lower(step.state, *batch_arrays).compile()
    compile_s = time.perf_counter() - tc
    log(f"compiled in {compile_s:.1f}s")

    flops, flops_source = None, "none"
    try:
        ca = compiled.cost_analysis()
        if ca and ca.get("flops", 0) > 0:
            flops, flops_source = float(ca["flops"]), "xla_cost_analysis"
    except Exception as e:
        log(f"cost_analysis unavailable: {e}")
    if flops is None:
        flops, flops_source = analytic_flops(), "analytic"
    elif scanned_hot_loop and flops < analytic_flops():
        # XLA cost analysis undercounts programs whose hot loop sits in
        # a lax.scan/while (it costs the body once, not trip_count
        # times) — the chunked vocab-chain / grad-accum steps hit this:
        # 4.6e12 counted vs the 6.1e12 model-analytic 6·P·T floor on
        # the GPT chunked headline.  Callers that KNOW their step scans
        # pass scanned_hot_loop=True; then take the larger of the two,
        # keep the flash complement the cost-analysis basis would have
        # carried, and label the source honestly.
        flops, flops_source = analytic_flops(), "analytic_model_floor"
        if pallas_attn_flops > 0:
            from apex_tpu.ops import pallas as pal
            if pal.pallas_mode() == "compiled":
                flops += pallas_attn_flops
                flops_source = "analytic_model_floor+flash_analytic"
    elif pallas_attn_flops > 0:
        # Whether flash actually carried the attention is a trace-time
        # fact, and pallas_mode() is exactly the predicate the kernel
        # dispatch used while this step was traced: 'compiled' on TPU.
        # The callers only pass
        # pallas_attn_flops for configs whose attention takes the flash
        # path when the kernel substrate is on (attn_dropout == 0).
        from apex_tpu.ops import pallas as pal
        if pal.pallas_mode() == "compiled":
            flops += pallas_attn_flops
            flops_source = "xla_cost_analysis+flash_analytic"
            log(f"flash attention FLOP complement: "
                f"+{pallas_attn_flops / 1e12:.3f} TFLOP/step")

    stage("warmup", f"{warmup} iters")
    state = step.state
    for i in range(warmup):
        state, loss = compiled(state, *batch_arrays)
        # per-iter (not once after the loop) so a watchdog fire names
        # the exact iteration and the stage log records whether the
        # step is slow or dead
        ti = time.perf_counter()
        jax.block_until_ready(state)
        stage("warmup", f"iter {i + 1}/{warmup} done "
                        f"({time.perf_counter() - ti:.1f}s)")
    lval = loss[0] if isinstance(loss, tuple) else loss
    log(f"warm, loss={float(lval):.4f}")

    stage("timing", f"{iters} iters")
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = compiled(state, *batch_arrays)
    jax.block_until_ready(state)
    dt = (time.perf_counter() - t0) / iters
    return dt, compile_s, flops, flops_source


def _lm_loss_fns(plain=False):
    """Token-level loss for the LM configs.  Default: the fused xentropy
    (contrib/xentropy) — forward saves logits + one lse scalar per row
    and backward reconstructs the softmax, instead of the plain path's
    materialized (T, V) log-softmax residual plus a (T, V) one-hot; at
    GPT vocab 50257 that residual is the single largest tensor in the
    step.  ``--plain-loss`` keeps the old path for A/B."""
    import jax.numpy as jnp
    from apex_tpu.nn import functional as F

    if plain:
        def token_losses(flat_logits, flat_labels):
            return F.cross_entropy(flat_logits, flat_labels,
                                   reduction="none")
    else:
        from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss

        def token_losses(flat_logits, flat_labels):
            # padding_idx=-1: no label id is ever -1, so nothing is
            # silently zeroed (the contrib default of 0 would mask a
            # real token id)
            return softmax_cross_entropy_loss(
                flat_logits, flat_labels, 0.0, -1, True)
    return token_losses


def build_bert_step(batch, seq_len, plain_loss=False, attn_dropout=0.0,
                    gathered_mlm=True):
    """BASELINE.md config 4 model+step+batch: BERT-base pretrain
    (masked-LM) with FusedLAMB + Pallas flash attention under the bf16
    fused step.  ``gathered_mlm`` (default): the reference pretraining
    recipe's masked_lm_positions convention — exactly
    max_predictions_per_seq = ceil(0.15*S) positions per sequence, MLM
    head + loss over the gathered (B, P) instead of all (B, S); the
    full-head arm stays as the A/B (``--full-mlm-head``).  Returns
    (step, batch_arrays, analytic_flops_fn, pallas_attn_flops)."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import bert_base
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.training import make_train_step

    stage("model_build", f"bert_base batch={batch} seq={seq_len} "
                         f"attn_drop={attn_dropout} "
                         f"gathered={gathered_mlm}")
    nn.manual_seed(0)
    vocab = 30522
    # default attn_dropout=0 keeps the headline config stable across
    # rounds; --attn-dropout 0.1 measures the original BERT recipe,
    # which since the in-kernel dropout work also rides flash (hash
    # mask).  Residual/embedding dropout stays on either way.
    model = bert_base(max_positions=seq_len, attn_dropout=attn_dropout)
    token_losses = _lm_loss_fns(plain_loss)
    opt = FusedLAMB(list(model.parameters()), lr=1e-3, weight_decay=0.01)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq_len)))
    if gathered_mlm:
        n_pred = -(-15 * seq_len // 100)       # ceil(0.15*S): 20 @ S=128

        def mlm_loss(logits, labels_g):
            # logits (B, P, V) over the gathered positions; every
            # position carries a label by construction
            flat = logits.reshape((-1, vocab))
            return jnp.mean(token_losses(flat, labels_g.reshape((-1,))))

        positions = np.stack([
            np.sort(rng.choice(seq_len, n_pred, replace=False))
            for _ in range(batch)])
        labels_g = jnp.asarray(rng.integers(0, vocab, (batch, n_pred)))
        step = make_train_step(model, opt, mlm_loss,
                               half_dtype=jnp.bfloat16, loss_scale=1.0)
        arrays = ((ids, jnp.asarray(positions)), labels_g)
    else:
        def mlm_loss(logits, labels):
            # full-head arm: ~15% of positions labeled (-100 = ignore)
            flat = logits.reshape((-1, vocab))
            lab = labels.reshape((-1,))
            mask = (lab >= 0).astype(jnp.float32)
            lab_safe = jnp.maximum(lab, 0)
            losses = token_losses(flat, lab_safe)
            return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)

        labels = np.full((batch, seq_len), -100, np.int32)
        pick = rng.random((batch, seq_len)) < 0.15
        labels[pick] = rng.integers(0, vocab, int(pick.sum()))
        step = make_train_step(model, opt, mlm_loss,
                               half_dtype=jnp.bfloat16, loss_scale=1.0)
        arrays = (ids, jnp.asarray(labels))

    # 6 * params * tokens per fwd+bwd step (the standard transformer
    # estimate), params ~110M
    return step, arrays, \
        lambda: 6.0 * 110e6 * batch * seq_len, \
        flash_attn_step_flops(
            [(12, batch, 12, seq_len, seq_len, 64, False)])


def run_bert_throughput(batch, seq_len, iters, warmup, plain_loss=False,
                        attn_dropout=0.0, gathered_mlm=True):
    step, arrays, af, paf = build_bert_step(batch, seq_len, plain_loss,
                                            attn_dropout, gathered_mlm)
    stage("compile", f"bert batch={batch}")
    return time_compiled_step(step, arrays, iters, warmup, af,
                              pallas_attn_flops=paf)


def run_seq2seq_throughput(batch, seq_len, iters, warmup,
                           plain_loss=False, loss_mode="chunked"):
    """Transformer-base seq2seq train step (copy-style synthetic pairs):
    sequences/sec through the fused bf16 step.  Default loss: the
    chunked vocab chain (the LM families' round-5 win), over the
    decoder hidden states + tied table."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import transformer_seq2seq
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    stage("model_build", f"seq2seq-base batch={batch} seq={seq_len} "
                         f"loss={loss_mode}")
    nn.manual_seed(0)
    vocab = 32000
    if plain_loss:
        loss_mode = "plain"
    chunked = loss_mode == "chunked"
    model = transformer_seq2seq(vocab_size=vocab, max_positions=seq_len,
                                attn_dropout=0.0, output_hidden=chunked)
    opt = FusedAdam(list(model.parameters()), lr=1e-3)

    if chunked:
        from apex_tpu.contrib.xentropy import make_chunked_lm_loss
        loss_fn = make_chunked_lm_loss(padding_idx=-1, shift=False)
    else:
        token_losses = _lm_loss_fns(loss_mode == "plain")

        def loss_fn(logits, tgt_out):
            return jnp.mean(token_losses(logits.reshape((-1, vocab)),
                                         tgt_out.reshape((-1,))))

    step = make_train_step(model, opt, loss_fn, half_dtype=jnp.bfloat16,
                           loss_scale=1.0)
    rng = np.random.default_rng(0)
    src_ids = jnp.asarray(rng.integers(1, vocab, (batch, seq_len)))
    tgt_in = jnp.concatenate(
        [jnp.zeros((batch, 1), src_ids.dtype), src_ids[:, :-1]], axis=1)

    stage("compile", f"seq2seq batch={batch}")
    # ~60M params transformer-base, 6 * params * (src+tgt) tokens
    return time_compiled_step(
        step, ((src_ids, tgt_in), src_ids), iters, warmup,
        lambda: 6.0 * 60e6 * batch * 2 * seq_len,
        # 6 enc self (full) + 6 dec self (causal) + 6 cross (full), h=8 d=64
        pallas_attn_flops=flash_attn_step_flops(
            [(6, batch, 8, seq_len, seq_len, 64, False),
             (6, batch, 8, seq_len, seq_len, 64, True),
             (6, batch, 8, seq_len, seq_len, 64, False)]),
        scanned_hot_loop=chunked)


def _lm_head_loss(loss_mode, vocab, chunk_rows=None):
    """(output_hidden, loss_fn) for an LM bench config.

    loss_mode selects the vocab-chain implementation — the round-5
    program-level A/B (VERDICT round 4 item 1):
      fused    materialized logits + contrib fused xentropy (round-4
               default)
      plain    materialized logits + F.cross_entropy
      chunked  output_hidden model + make_chunked_lm_loss: head matmul,
               loss and gradient run per row-chunk in one loop, (N, V)
               never materializes
      kernel   output_hidden model + the Pallas fused lm-head+loss
               kernel (ops/pallas/lm_head_xent) wired INTO the step —
               round 4 only ever measured it against the isolated chain
    """
    import jax.numpy as jnp

    if loss_mode in ("fused", "plain"):
        token_losses = _lm_loss_fns(loss_mode == "plain")

        def lm_loss(logits, ids):
            # logits.shape[-1] is the (possibly lane-padded) vocab
            # width; pad columns are -1e30-masked, so the loss over
            # them is exact
            flat = logits[:, :-1].reshape((-1, logits.shape[-1]))
            tgt = ids[:, 1:].reshape((-1,))
            return jnp.mean(token_losses(flat, tgt))
        return False, lm_loss
    if loss_mode == "chunked":
        from apex_tpu.contrib.xentropy import make_chunked_lm_loss
        return True, make_chunked_lm_loss(vocab_size=vocab,
                                          padding_idx=-1,
                                          chunk_rows=chunk_rows)
    if loss_mode == "kernel":
        from apex_tpu.ops.pallas.lm_head_xent import fused_lm_head_xent

        def kernel_loss(out, ids):
            hidden, table = out
            flat = hidden[:, :-1].reshape((-1, hidden.shape[-1]))
            tgt = ids[:, 1:].reshape((-1,))
            return jnp.mean(fused_lm_head_xent(flat, table, tgt))
        return True, kernel_loss
    raise ValueError(f"unknown loss_mode {loss_mode!r}")


def build_gpt_step(batch, seq_len, remat=False, size="small",
                   loss_mode="chunked", attn_dropout=0.0, pad_vocab=False,
                   grad_accum=1, chunk_rows=None, dynamic_scale=False):
    """GPT-2 causal-LM model+step+batch: next-token loss with FusedAdam
    under the bf16 fused step (the autoregressive counterpart of the BERT
    config; no reference analogue — the reference ships no LMs)."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import gpt2_medium, gpt2_small
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    factory, n_params = ((gpt2_medium, 355e6) if size == "medium"
                         else (gpt2_small, 124e6))
    stage("model_build", f"gpt2_{size} batch={batch} seq={seq_len} "
                         f"attn_drop={attn_dropout} loss={loss_mode}")
    nn.manual_seed(0)
    vocab = 50257
    # default attn_dropout=0 keeps the headline config stable across
    # rounds (modern LM recipes train without it); --attn-dropout 0.1
    # measures the historical GPT-2 recipe, which since the in-kernel
    # dropout work ALSO rides flash (hash mask, no (S,S) tensor) —
    # residual/embedding dropout stays on either way
    # --pad-vocab: Megatron's make-vocab-size-divisible-by convention
    # (50257 -> 50304): the head matmul tiles the MXU lane-aligned; the
    # loss sees -1e30-masked pad columns, so numerics are exact
    if pad_vocab and loss_mode == "kernel":
        raise ValueError(
            "--loss-mode kernel with --pad-vocab is unsupported: the "
            "fused lm-head kernel computes plain CE over the table's "
            "full height and would treat the pad rows as real vocab "
            "(the chunked mode masks them; use chunked or fused)")
    output_hidden, lm_loss = _lm_head_loss(loss_mode, vocab, chunk_rows)
    model = factory(max_positions=seq_len, attn_dropout=attn_dropout,
                    remat=remat,
                    pad_vocab_multiple=128 if pad_vocab else None,
                    output_hidden=output_hidden)
    opt = FusedAdam(list(model.parameters()), lr=6e-4, weight_decay=0.1)

    # --dynamic-scale: the reference's signature fp16 machinery (scaled
    # loss, per-step unscale + overflow check + conditional skip,
    # amp/scaler.py) priced on-chip against the bf16 loss_scale=1.0
    # fast path that skips the non-finite reduction entirely
    step = make_train_step(model, opt, lm_loss,
                           half_dtype=jnp.bfloat16,
                           loss_scale="dynamic" if dynamic_scale else 1.0,
                           grad_accum_steps=grad_accum)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq_len)))

    layers, heads = (24, 16) if size == "medium" else (12, 12)
    # 6 * params * tokens (fwd+bwd)
    return step, (ids, ids), \
        lambda: 6.0 * n_params * batch * seq_len, \
        flash_attn_step_flops(
            [(layers, batch, heads, seq_len, seq_len, 64, True)])


def run_gpt_throughput(batch, seq_len, iters, warmup, remat=False,
                       size="small", loss_mode="chunked", attn_dropout=0.0,
                       pad_vocab=False, grad_accum=1, chunk_rows=None,
                       dynamic_scale=False):
    step, arrays, af, paf = build_gpt_step(batch, seq_len, remat, size,
                                           loss_mode, attn_dropout,
                                           pad_vocab, grad_accum,
                                           chunk_rows, dynamic_scale)
    stage("compile", f"gpt batch={batch}")
    return time_compiled_step(step, arrays, iters, warmup, af,
                              pallas_attn_flops=paf,
                              scanned_hot_loop=(loss_mode == "chunked"
                                                or grad_accum > 1))


def build_llama_step(batch, seq_len, remat=False, loss_mode="chunked",
                     grad_accum=1, chunk_rows=None):
    """Llama-style ~125M causal LM (RoPE + RMSNorm + SwiGLU + GQA 12q/4kv)
    with FusedAdam under the bf16 fused step — the modern-architecture
    counterpart of the GPT-2 config (attention always takes the causal
    flash path: the family has no attention dropout by construction)."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import LlamaModel
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    stage("model_build", f"llama_125m batch={batch} seq={seq_len}")
    nn.manual_seed(0)
    vocab = 32000
    layers, heads, hidden = 12, 12, 768
    output_hidden, lm_loss = _lm_head_loss(loss_mode, vocab, chunk_rows)
    model = LlamaModel(vocab_size=vocab, hidden=hidden, layers=layers,
                       heads=heads, kv_heads=4, intermediate=2048,
                       max_positions=max(seq_len, 128), remat=remat,
                       output_hidden=output_hidden)
    model.train()
    # analytic 6·P·T counts MATMUL params: the token-embedding gather
    # does no MXU work (the GPT family's tied head makes its table a
    # matmul param; Llama's untied lm_head is counted, tok_emb is not)
    n_params = sum(int(np.prod(p.data.shape)) for p in model.parameters()) \
        - int(np.prod(model.tok_emb.weight.data.shape))
    opt = FusedAdam(list(model.parameters()), lr=6e-4, weight_decay=0.1)

    step = make_train_step(model, opt, lm_loss,
                           half_dtype=jnp.bfloat16, loss_scale=1.0,
                           grad_accum_steps=grad_accum)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq_len)))
    return step, (ids, ids), \
        lambda: 6.0 * n_params * batch * seq_len, \
        flash_attn_step_flops(
            [(layers, batch, heads, seq_len, seq_len, hidden // heads,
              True)])


def run_llama_throughput(batch, seq_len, iters, warmup, remat=False,
                         loss_mode="chunked", grad_accum=1,
                         chunk_rows=None):
    step, arrays, af, paf = build_llama_step(batch, seq_len, remat,
                                             loss_mode, grad_accum,
                                             chunk_rows)
    stage("compile", f"llama batch={batch}")
    return time_compiled_step(step, arrays, iters, warmup, af,
                              pallas_attn_flops=paf,
                              scanned_hot_loop=(loss_mode == "chunked"
                                                or grad_accum > 1))


def _markov_ids(nxt, n, seq_len, rng, active):
    """Batch of sequences from the fixed successor map ``nxt`` over the
    first ``active`` token ids (deterministic chains — a trained LM's
    argmax becomes the successor, so a trained draft can actually agree
    with a trained target)."""
    import numpy as np
    ids = np.empty((n, seq_len), np.int64)
    ids[:, 0] = rng.integers(0, active, n)
    for t in range(1, seq_len):
        ids[:, t] = nxt[ids[:, t - 1]]
    return ids


def _train_on_markov(model, nxt, active, steps, batch, seq_len, rng,
                     lr=3e-4):
    """Train ``model`` on the successor task for ``steps`` steps (fused
    bf16 step, fused-xentropy loss on the model's own logits) and write
    the weights back.  Returns final-step loss."""
    import jax.numpy as jnp

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    model.train()
    token_losses = _lm_loss_fns(False)
    vocab = model.lm_head.weight.data.shape[0]

    def lm_loss(logits, ids):
        flat = logits[:, :-1].reshape((-1, vocab))
        tgt = ids[:, 1:].reshape((-1,))
        return jnp.mean(token_losses(flat, tgt))

    opt = FusedAdam(list(model.parameters()), lr=lr)
    step = make_train_step(model, opt, lm_loss, half_dtype=jnp.bfloat16,
                           loss_scale=1.0)
    loss = None
    for i in range(steps):
        ids = jnp.asarray(_markov_ids(nxt, batch, seq_len, rng, active))
        loss = step(ids, ids)
        if i % 50 == 0:
            log(f"  markov train step {i}: loss={float(loss):.4f}")
    step.sync_to_objects()
    model.eval()
    return float(loss)


def run_spec_decode_throughput(batch, seq_len, new_tokens=128, k=4,
                               int8_draft=True, draft_mode="trained",
                               draft_train_steps=400):
    """Speculative vs plain greedy decode on the Llama ~125M config:
    a 2-layer draft proposes, the target verifies chunks of k+1 — the
    output is bit-identical (asserted), only the speed differs.

    ``draft_mode`` sets the acceptance operating point (VERDICT r4 #2 —
    the random-weights arm's acceptance 0.0 made the ratio an overhead
    floor, not a demo):
      trained  train target AND draft at bench time on a deterministic
               successor task (2048 active ids of the 32k vocab), so
               draft-target argmax agreement — and the measured
               acceptance — is real; ``draft_train_steps`` tunes the
               draft's operating point (fewer steps = lower acceptance)
      random   the historical overhead-floor arm (acceptance ~0)
    Returns (spec_toks_per_s, plain_toks_per_s, compile_s, stats)."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.inference import quantize_int8, speculative_generate
    from apex_tpu.models import LlamaModel, generate

    stage("model_build", f"llama spec-decode batch={batch} k={k} "
                         f"draft={draft_mode}")
    nn.manual_seed(0)
    vocab = 32000
    s_max = seq_len + new_tokens + k + 1
    target = LlamaModel(vocab_size=vocab, hidden=768, layers=12, heads=12,
                        kv_heads=4, intermediate=2048,
                        max_positions=max(s_max, 128)).eval()
    nn.manual_seed(1)
    draft = LlamaModel(vocab_size=vocab, hidden=256, layers=2, heads=4,
                       kv_heads=2, intermediate=704,
                       max_positions=max(s_max, 128)).eval()
    rng = np.random.default_rng(0)
    if draft_mode == "trained":
        active = 2048
        nxt = rng.permutation(active)
        stage("train", f"target on successor task")
        lt = _train_on_markov(target, nxt, active, 300, 32, 128, rng)
        stage("train", f"draft ({draft_train_steps} steps)")
        ld = _train_on_markov(draft, nxt, active, draft_train_steps,
                              32, 128, rng, lr=1e-3)
        log(f"trained: target loss {lt:.4f}, draft loss {ld:.4f}")
        prompt = jnp.asarray(_markov_ids(nxt, batch, seq_len, rng,
                                         active))
    else:
        prompt = jnp.asarray(rng.integers(0, vocab, (batch, seq_len)))
    if int8_draft:
        quantize_int8(draft)

    stage("compile", "plain generate")
    tc = time.perf_counter()
    base = generate(target, prompt, new_tokens)
    int(jnp.sum(base))
    stage("compile", "speculative generate")
    spec, spec_stats = speculative_generate(target, draft, prompt,
                                            new_tokens, k=k,
                                            return_stats=True)
    int(jnp.sum(spec))
    compile_s = time.perf_counter() - tc
    log(f"compiled both in {compile_s:.1f}s")
    # the guarantee is exact up to floating-point argmax ties between
    # the chunked and single-token attention programs (one shared body,
    # but XLA may reduce the two shapes differently on the MXU); ONE tie
    # flip cascades the whole tail, so prefix agreement is the wrong
    # gate on hardware (round 4: a position-147 flip failed it while the
    # algorithm was fine).  The non-cascading check is teacher-forced:
    # re-run the target over each arm's own output and count positions
    # where the emitted token disagrees with the target's argmax on that
    # same prefix — a tie costs 1 mismatch, a real accept-logic bug
    # mismatches nearly everywhere (1 - 1/V of positions).
    first_diff = int(jnp.sum(jnp.cumprod(
        (base == spec).all(0).astype(jnp.int32))))
    log(f"greedy/speculative agree on first {first_diff}/"
        f"{base.shape[1]} positions (informational)")

    import jax as _jax

    from apex_tpu.nn.modules import Ctx

    # params ride as jit ARGUMENTS (the decode entry points' ctx-env
    # convention) — closing over the module would inline 125M weights
    # as HLO constants and blow the remote-compile payload
    t_params = list(target.parameters()) + list(target.buffers())
    t_vals = [q.data for q in t_params]

    @_jax.jit
    def _tf_mismatches(vals, toks):
        ctx = Ctx(env={id(o): v for o, v in zip(t_params, vals)},
                  stats_out={}, training=False)
        logits = target.forward(ctx, toks[:, :-1])
        pred = jnp.argmax(logits[:, seq_len - 1:], axis=-1)
        return jnp.sum(pred != toks[:, seq_len:])

    n_gen = batch * new_tokens
    mm_base = int(_tf_mismatches(t_vals, base))
    mm_spec = int(_tf_mismatches(t_vals, spec))
    log(f"teacher-forced mismatches: base {mm_base}/{n_gen}, "
        f"spec {mm_spec}/{n_gen}")
    if mm_spec > mm_base + max(2, n_gen // 16):
        raise AssertionError(
            f"speculative decode disagrees with the target's own argmax "
            f"at {mm_spec}/{n_gen} positions (plain decode: {mm_base}) — "
            f"more than argmax-tie noise")

    # acceptance telemetry (VERDICT r3 #5: log it with the A/B): with
    # random weights the draft rarely matches the target argmax, so the
    # measured ratio is the overhead floor, not a trained-draft speedup
    stats = spec_stats
    log(f"speculative rounds={stats['rounds']} "
        f"tokens/round={stats['tokens_per_round']:.2f} "
        f"draft_acceptance={stats['draft_acceptance']:.3f}")

    stage("timing", "3 calls each arm")
    t0 = time.perf_counter()
    for _ in range(3):
        out = generate(target, prompt, new_tokens)
        int(jnp.sum(out))
    dt_plain = (time.perf_counter() - t0) / 3
    t0 = time.perf_counter()
    for _ in range(3):
        out = speculative_generate(target, draft, prompt, new_tokens, k=k)
        int(jnp.sum(out))
    dt_spec = (time.perf_counter() - t0) / 3
    toks = batch * new_tokens
    return toks / dt_spec, toks / dt_plain, compile_s, stats


def run_decode_throughput(batch, seq_len, new_tokens=128, int8=False,
                          kv_int8=False):
    """Greedy KV-cache decode tokens/s (gpt2-small): one warm compiled
    call timed via value fetch.  ``int8=True`` quantizes the weight
    matrices (weight-only w8a16, inference/quant.py) first — decode is
    HBM-bound, so halved weight bytes should show as tokens/s;
    ``kv_int8=True`` additionally quantizes the KV cache
    (cache_dtype="int8"), the long-context traffic lever."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import generate, gpt2_small

    stage("model_build", f"gpt2_small decode batch={batch}"
          + (" int8" if int8 else "") + (" kv-int8" if kv_int8 else ""))
    nn.manual_seed(0)
    model = gpt2_small(max_positions=seq_len + new_tokens,
                       attn_dropout=0.0, dropout=0.0)
    model.eval()
    if int8:
        from apex_tpu.inference import quantize_int8
        quantize_int8(model)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 50257, (batch, seq_len)))

    cache_dtype = "int8" if kv_int8 else None
    stage("compile", f"decode scan over {seq_len + new_tokens} positions")
    tc = time.perf_counter()
    out = generate(model, prompt, new_tokens, cache_dtype=cache_dtype)
    int(jnp.sum(out))                       # fetch = sync
    compile_s = time.perf_counter() - tc
    log(f"compiled in {compile_s:.1f}s")

    stage("timing", "3 decode calls")
    t0 = time.perf_counter()
    for _ in range(3):
        out = generate(model, prompt, new_tokens, cache_dtype=cache_dtype)
        int(jnp.sum(out))
    dt = (time.perf_counter() - t0) / 3
    toks_per_sec = batch * new_tokens / dt
    return toks_per_sec, dt, compile_s


def run_llama_decode_throughput(batch, seq_len, new_tokens=128,
                                int8=False, kv_int8=False, window=None):
    """Greedy KV-cache decode tokens/s on the llama_125m geometry (GQA
    4-kv-head cache).  ``window=w`` builds the Mistral-band model whose
    decode runs the ROLLING cache (inference/rolling.py): cache reads
    per token drop from O(context) to O(window) — the A/B against the
    unwindowed run is the rolling cache's reason-to-exist number."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import LlamaModel, generate

    stage("model_build",
          f"llama_125m decode batch={batch} window={window}"
          + (" int8" if int8 else "") + (" kv-int8" if kv_int8 else ""))
    nn.manual_seed(0)
    model = LlamaModel(vocab_size=32000, hidden=768, layers=12, heads=12,
                       kv_heads=4, intermediate=2048,
                       max_positions=seq_len + new_tokens,
                       sliding_window=window)
    model.eval()
    if int8:
        from apex_tpu.inference import quantize_int8
        quantize_int8(model)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 32000, (batch, seq_len)))

    cache_dtype = "int8" if kv_int8 else None
    stage("compile", f"decode scan over {seq_len + new_tokens} positions")
    tc = time.perf_counter()
    out = generate(model, prompt, new_tokens, cache_dtype=cache_dtype)
    int(jnp.sum(out))                       # fetch = sync
    compile_s = time.perf_counter() - tc
    log(f"compiled in {compile_s:.1f}s")

    stage("timing", "3 decode calls")
    t0 = time.perf_counter()
    for _ in range(3):
        out = generate(model, prompt, new_tokens, cache_dtype=cache_dtype)
        int(jnp.sum(out))
    dt = (time.perf_counter() - t0) / 3
    return batch * new_tokens / dt, dt, compile_s


def build_vit_step(batch):
    """ViT-S/16 at 224 (~22M params), AdamW-style FusedAdam under the
    bf16 fused step — the vision-transformer counterpart of the ResNet
    headline (attention at 197 tokens rides the XLA path per the
    shape-aware dispatch, so cost analysis sees every matmul)."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import vit_small
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    stage("model_build", f"vit_small batch={batch}")
    nn.manual_seed(0)
    model = vit_small(num_classes=1000)
    n_params = sum(int(np.prod(p.data.shape)) for p in model.parameters())
    opt = FusedAdam(list(model.parameters()), lr=1e-3, adam_w_mode=True,
                    weight_decay=0.05)
    step = make_train_step(
        model, opt, lambda out, y: F.cross_entropy(out, y),
        half_dtype=jnp.bfloat16, loss_scale=1.0)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 3, 224, 224)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)))
    # 6ND-style fallback only (N params x D tokens: 197 per image);
    # cost analysis sees the whole program on the normal path
    tokens = (224 // 16) ** 2 + 1
    return step, (x, y), (lambda: 6.0 * n_params * batch * tokens), 0.0


def run_vit_throughput(batch, iters, warmup):
    step, arrays, af, _ = build_vit_step(batch)
    stage("compile", f"vit batch={batch}")
    return time_compiled_step(step, arrays, iters, warmup, af)


def build_dcgan_step(batch, image_size=64, nz=100, ngf=64, ndf=64):
    """DCGAN multi-model/multi-loss amp iteration — BASELINE config 5
    (reference examples/dcgan/main_amp.py:214-253: two models, two
    optimizers, three scaled losses).  Canonical 64x64 DCGAN geometry;
    the whole D-real/D-fake/G iteration compiles into ONE executable
    via make_gan_train_step with the example's O1-equivalent settings
    (fp32 params, dynamic loss scale)."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_gan_train_step

    stage("model_build", f"dcgan{image_size} batch={batch}")
    nn.manual_seed(0)
    netG = nn.Sequential(
        nn.ConvTranspose2d(nz, ngf * 8, 4, stride=1, padding=0),
        nn.BatchNorm2d(ngf * 8), nn.ReLU(),
        nn.ConvTranspose2d(ngf * 8, ngf * 4, 4, stride=2, padding=1),
        nn.BatchNorm2d(ngf * 4), nn.ReLU(),
        nn.ConvTranspose2d(ngf * 4, ngf * 2, 4, stride=2, padding=1),
        nn.BatchNorm2d(ngf * 2), nn.ReLU(),
        nn.ConvTranspose2d(ngf * 2, ngf, 4, stride=2, padding=1),
        nn.BatchNorm2d(ngf), nn.ReLU(),
        nn.ConvTranspose2d(ngf, 3, 4, stride=2, padding=1),
        nn.Tanh())
    netD = nn.Sequential(
        nn.Conv2d(3, ndf, 4, stride=2, padding=1), nn.LeakyReLU(0.2),
        nn.Conv2d(ndf, ndf * 2, 4, stride=2, padding=1),
        nn.BatchNorm2d(ndf * 2), nn.LeakyReLU(0.2),
        nn.Conv2d(ndf * 2, ndf * 4, 4, stride=2, padding=1),
        nn.BatchNorm2d(ndf * 4), nn.LeakyReLU(0.2),
        nn.Conv2d(ndf * 4, ndf * 8, 4, stride=2, padding=1),
        nn.BatchNorm2d(ndf * 8), nn.LeakyReLU(0.2),
        nn.Conv2d(ndf * 8, 1, 4, stride=1, padding=0), nn.Flatten(0))
    optD = FusedAdam(list(netD.parameters()), lr=2e-4, betas=(0.5, 0.999))
    optG = FusedAdam(list(netG.parameters()), lr=2e-4, betas=(0.5, 0.999))

    def d_loss(out_r, out_f):
        return (F.binary_cross_entropy_with_logits(
                    out_r, jnp.ones_like(out_r))
                + F.binary_cross_entropy_with_logits(
                    out_f, jnp.zeros_like(out_f)))

    def g_loss(out_f):
        return F.binary_cross_entropy_with_logits(
            out_f, jnp.ones_like(out_f))

    step = make_gan_train_step(netD, netG, optD, optG, d_loss, g_loss,
                               half_dtype=None, loss_scale="dynamic")
    rng = np.random.default_rng(0)
    real = jnp.asarray(
        rng.standard_normal((batch, 3, image_size, image_size)),
        jnp.float32)
    z = jnp.asarray(rng.standard_normal((batch, nz, 1, 1)), jnp.float32)

    def _conv_flops(cin, cout, k, hout):
        return 2.0 * cin * cout * k * k * hout * hout

    g_f = sum(_conv_flops(*a) for a in
              ((nz, ngf * 8, 4, 4), (ngf * 8, ngf * 4, 4, 8),
               (ngf * 4, ngf * 2, 4, 16), (ngf * 2, ngf, 4, 32),
               (ngf, 3, 4, 64)))
    d_f = sum(_conv_flops(*a) for a in
              ((3, ndf, 4, 32), (ndf, ndf * 2, 4, 16),
               (ndf * 2, ndf * 4, 4, 8), (ndf * 4, ndf * 8, 4, 4),
               (ndf * 8, 1, 4, 1)))
    # coarse fwd+bwd(~3x fwd) over: D on real+fake, G once for the D
    # loss (detached) + the G-loss path through both nets — cost
    # analysis replaces this whenever available
    analytic = lambda: 3.0 * batch * (2.0 * g_f + 3.0 * d_f)
    return step, (real, z), analytic


def run_dcgan_throughput(batch, iters, warmup):
    step, arrays, af = build_dcgan_step(batch)
    stage("compile", f"dcgan batch={batch}")
    return time_compiled_step(step, arrays, iters, warmup, af)


def build_resnet_step(batch, nhwc=False, flat_optim=False):
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import resnet50
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.training import make_train_step

    stage("model_build", f"resnet50 batch={batch} nhwc={nhwc} "
                         f"flat={flat_optim}")
    nn.manual_seed(0)
    model = resnet50(num_classes=1000)
    if nhwc:
        # channels-last A/B arm: same OIHW weights, NHWC activations
        # end-to-end (nn.to_channels_last) — the conv-layout MFU lever
        nn.to_channels_last(model)
    opt = FusedSGD(list(model.parameters()), lr=0.1, momentum=0.9,
                   weight_decay=1e-4)
    step = make_train_step(
        model, opt, lambda out, y: F.cross_entropy(out, y),
        half_dtype=jnp.bfloat16, loss_scale=1.0,
        flat_master=flat_optim)

    rng = np.random.default_rng(0)
    shape = (batch, 224, 224, 3) if nhwc else (batch, 3, 224, 224)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)))

    return step, (x, y), (lambda: resnet50_step_flops(batch)), 0.0


def run_throughput(batch, iters, warmup, nhwc=False,
                   flat_optim=False):
    step, arrays, af, _ = build_resnet_step(batch, nhwc=nhwc,
                                            flat_optim=flat_optim)
    stage("compile", f"batch={batch}")
    return time_compiled_step(step, arrays, iters, warmup, af)


def opt_microbench_records(sizes=(1_000_000, 10_000_000), n_tensors=32,
                           warmup=3, timed_steps=20):
    """``opt_step_us`` microbench: FusedAdam steps/sec through the
    step-program cache vs the pre-cache per-dtype-bucket dispatch.

    Runs entirely on CPU (forced below) — the quantity under test is
    host dispatch + program count, which the CPU backend exercises the
    same way.  Returns a list of JSON-able records.
    """
    import functools as _ft

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import ops
    from apex_tpu.nn import Parameter
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.runtime import step_cache

    # the pre-cache dispatch, verbatim (old optimizers/fused_adam.py:15-24):
    # one jitted executable per dtype bucket, static hyperparameters
    @_ft.partial(jax.jit, static_argnames=(
        "beta1", "beta2", "eps", "mode", "bias_correction", "weight_decay"))
    def _prebucket_step(flag, lists, lr, step, beta1, beta2, eps, mode,
                        bias_correction, weight_decay):
        return ops.multi_tensor_adam(flag, lists, lr, beta1, beta2, eps,
                                     step, mode, bias_correction,
                                     weight_decay)

    records = []
    for total in sizes:
        per = total // n_tensors
        rng = np.random.default_rng(0)

        def make_params():
            out = []
            for _ in range(n_tensors):
                p = Parameter(jnp.asarray(
                    rng.standard_normal(per), jnp.float32))
                p.grad = jnp.asarray(rng.standard_normal(per), jnp.float32)
                out.append(p)
            return out

        def record(mode, dt_s, steps):
            us = dt_s / steps * 1e6
            records.append({
                "metric": "opt_step_us", "config": f"fused_adam_{total}",
                "params": total, "tensors": n_tensors, "mode": mode,
                "platform": "cpu", "opt_step_us": round(us, 1),
                "steps_per_sec": round(steps / dt_s, 2)})

        # -- after: the step cache (1 executable, donated, traced hypers) --
        params = make_params()
        opt = FusedAdam(params, lr=1e-3, weight_decay=0.01)
        for _ in range(warmup):
            opt.step()
        jax.block_until_ready(params[0].data)
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            opt.step()
        jax.block_until_ready(params[0].data)
        record("step_cache", time.perf_counter() - t0, timed_steps)

        # -- before: per-bucket dispatch, fresh arrays each rebind ---------
        ps = [jnp.asarray(rng.standard_normal(per), jnp.float32)
              for _ in range(n_tensors)]
        gs = [jnp.asarray(rng.standard_normal(per), jnp.float32)
              for _ in range(n_tensors)]
        ms = [jnp.zeros_like(p) for p in ps]
        vs = [jnp.zeros_like(p) for p in ps]
        flag = ops.zero_flag()

        def one_prebucket(i, ps, ms, vs):
            _, ps, ms, vs = _prebucket_step(
                flag, [gs, ps, ms, vs], jnp.asarray(1e-3, jnp.float32),
                jnp.asarray(i + 1, jnp.int32), 0.9, 0.999, 1e-8, 1, True,
                0.01)
            return ps, ms, vs

        for i in range(warmup):
            ps, ms, vs = one_prebucket(i, ps, ms, vs)
        jax.block_until_ready(ps[0])
        t0 = time.perf_counter()
        for i in range(timed_steps):
            ps, ms, vs = one_prebucket(i, ps, ms, vs)
        jax.block_until_ready(ps[0])
        record("per_bucket", time.perf_counter() - t0, timed_steps)

        cached, bucket = records[-2], records[-1]

        # -- the retrace pathology the cache removes: a weight-decay
        # schedule through the static-hyper pre-cache path recompiles
        # EVERY step (satellite fix: hyperparameters are traced scalars,
        # so the step-cache path above is schedule-invariant) -----------
        sched_steps = 5
        t0 = time.perf_counter()
        for i in range(sched_steps):
            _, ps, ms, vs = _prebucket_step(
                flag, [gs, ps, ms, vs], jnp.asarray(1e-3, jnp.float32),
                jnp.asarray(i + 1, jnp.int32), 0.9, 0.999, 1e-8, 1, True,
                0.01 * (1.0 + i))
        jax.block_until_ready(ps[0])
        record("per_bucket_wd_schedule_retrace",
               time.perf_counter() - t0, sched_steps)
        records.append({
            "metric": "opt_step_us_speedup",
            "config": f"fused_adam_{total}", "params": total,
            "platform": "cpu",
            "value": round(bucket["opt_step_us"] / cached["opt_step_us"], 3),
            "unit": "x_per_bucket_over_step_cache",
            "step_cache_stats": step_cache.stats()["by_kind"].get(
                "fused_adam", {})})
    return records


def run_opt_microbench(args):
    stage("opt_microbench", "FusedAdam 1M/10M params, cpu")
    for rec in opt_microbench_records():
        emit(rec)
    return 0


def accum_microbench_records(ks=(1, 4, 16), dim=256, micro_batch=8,
                             warmup=2, timed_windows=10):
    """``accum_step_us`` microbench: the one-executable accumulation window
    (``make_train_step(accum_steps=K)``) at K ∈ {1, 4, 16}.

    CPU-forced like ``--opt-microbench``: the quantities under test are
    host dispatch count and program count per window — ``step_cache``
    pins dispatches-per-window at 1 for every K, which is the tentpole
    claim (K microbatches of work, O(1) dispatches).  ``accum_step_us``
    is the wall time of one whole window (so it grows ~linearly in K on
    CPU; the win is the flat dispatch/exchange count, not window time).
    Returns a list of JSON-able records.
    """
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.runtime import step_cache
    from apex_tpu.training import make_train_step

    records = []
    rng = np.random.default_rng(0)
    for k in ks:
        nn.manual_seed(0)
        model = nn.Sequential(nn.Linear(dim, dim), nn.ReLU(),
                              nn.Linear(dim, dim), nn.ReLU(),
                              nn.Linear(dim, 10))
        opt = FusedSGD(list(model.parameters()), lr=0.1, momentum=0.9)
        step = make_train_step(model, opt,
                               lambda o, t: F.cross_entropy(o, t),
                               half_dtype=jnp.bfloat16,
                               loss_scale="dynamic",
                               accum_steps=k, accum_stacked=(k > 1))
        if k > 1:
            x = jnp.asarray(rng.standard_normal((k, micro_batch, dim)),
                            jnp.float32)
            y = jnp.asarray(rng.integers(0, 10, (k, micro_batch)))
        else:
            x = jnp.asarray(rng.standard_normal((micro_batch, dim)),
                            jnp.float32)
            y = jnp.asarray(rng.integers(0, 10, (micro_batch,)))
        for _ in range(warmup):
            step(x, y)
        jax.block_until_ready(step.state.master_params[0])
        step_cache.reset_stats()
        t0 = time.perf_counter()
        for _ in range(timed_windows):
            step(x, y)
        jax.block_until_ready(step.state.master_params[0])
        dt = time.perf_counter() - t0
        st = step_cache.stats()["by_kind"].get("train_step", {})
        records.append({
            "metric": "accum_step_us", "config": f"mlp_accum_k{k}",
            "accum_steps": k, "micro_batch": micro_batch,
            "platform": "cpu",
            "accum_step_us": round(dt / timed_windows * 1e6, 1),
            "accum_step_us_per_microbatch":
                round(dt / timed_windows / k * 1e6, 1),
            "dispatches_per_window":
                round(st.get("dispatches", 0) / timed_windows, 3),
            "compiles_in_timed_region": st.get("compiles", 0)})
    return records


def run_accum_microbench(args):
    stage("accum_microbench",
          "one-executable accumulation window, K in {1,4,16}, cpu")
    for rec in accum_microbench_records():
        emit(rec)
    return 0


def register_record(rec):
    """Mirror a bench record into the apex_tpu.observe registry as a
    ``bench.<metric>`` event — one durable telemetry stream for bench
    rounds and training runs alike.  The emitted JSON keys above stay
    exactly as they are (the alias, kept for one release) so existing
    ledger parsers keep working.  Import is call-time: bench.py must
    stay importable without apex_tpu on the path."""
    try:
        from apex_tpu.observe import event
    except Exception:
        return
    event("bench." + str(rec.get("metric", "record")), **rec)


class StageLedger:
    """Resumable per-stage completion ledger (``--ledger path.json``).

    A bench round is a sequence of independent stages; without a
    ledger one hung stage (a backend probe that never returns, a
    watchdog ``os._exit``) forces re-running EVERYTHING, stages that
    already passed included.  The ledger records each stage's terminal
    status in a JSON file written atomically (tmp + fsync + rename, the
    checkpoint discipline in miniature), so a re-run with the same
    ledger skips ``done`` stages and re-runs only the hung/failed ones
    — a stage
    that hard-exits mid-run is left marked ``running``, which does NOT
    count as done.  ``--stages a,b,c`` drives several stages through one
    ledger in one invocation."""

    def __init__(self, path):
        self.path = path
        self.stages = {}
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    self.stages = json.load(f).get("stages", {})
            except (OSError, ValueError) as e:
                log(f"ledger: unreadable ({e}); starting fresh")
                self.stages = {}

    def status(self, name):
        return self.stages.get(name, {}).get("status")

    def is_done(self, name):
        return self.status(name) == "done"

    def mark(self, name, status, **extra):
        rec = {"status": status,
               "elapsed_s": round(time.perf_counter() - T0, 1)}
        rec.update(extra)
        self.stages[name] = rec
        self._write()

    def _write(self):
        if not self.path:
            return
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"stages": self.stages}, f, indent=2,
                      sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)

    def run(self, name, fn):
        """Run ``fn`` under ``name`` unless already done; returns its
        rc (0 for a skip).  Failures — nonzero rc or an exception — are
        recorded as ``failed`` and the exception propagates."""
        if self.is_done(name):
            log(f"ledger: stage {name} done -- skipping")
            return 0
        self.mark(name, "running")
        try:
            rc = fn()
        except BaseException as e:
            self.mark(name, "failed",
                      error=f"{type(e).__name__}: {e}")
            raise
        self.mark(name, "done" if rc == 0 else "failed", rc=rc)
        return rc


def observe_microbench_records(drain_everys=(1, 16), dim=512,
                               micro_batch=512, warmup=2, timed_steps=10,
                               repeats=3):
    """``telemetry_overhead_us`` microbench: the fused step with the
    on-device telemetry carry (per-window loss / grad-norm / loss-scale /
    overflow accumulation + a drain every ``drain_every`` windows) vs the
    same step with telemetry off.

    CPU-forced like the other microbenches — the quantity under test is
    the *extra* on-device accumulation plus the host drain, both of
    which exist on every backend.  Arms are timed INTERLEAVED, base
    then each telemetry arm within every repeat, and the overhead is
    the median across repeats of the paired per-repeat differences —
    a load spike that smears one repeat hits both arms of that repeat
    equally instead of landing on whichever arm happened to run last
    (the min-of-repeats-per-arm predecessor timed the base arm to
    completion first and flaked under CI contention).  Each record
    carries ``base_spread_pct`` (max-min over median of the base
    timings) so consumers can see the noise floor the measurement was
    taken on.  The config is sized so the model's fwd/bwd dominates
    (CPU XLA's unfused O(P) grad-norm reduce is ~300us flat; a toy
    step would blame that on telemetry): the observe claim is that at
    ``drain_every >= 16`` the overhead is under 2% of step time.
    """
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.training import make_train_step

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((micro_batch, dim)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, (micro_batch,)))

    def build(telemetry, drain_every):
        nn.manual_seed(0)
        model = nn.Sequential(nn.Linear(dim, dim), nn.ReLU(),
                              nn.Linear(dim, dim), nn.ReLU(),
                              nn.Linear(dim, 10))
        opt = FusedSGD(list(model.parameters()), lr=0.1, momentum=0.9)
        return make_train_step(model, opt,
                               lambda o, t: F.cross_entropy(o, t),
                               half_dtype=jnp.bfloat16,
                               loss_scale="dynamic",
                               telemetry=telemetry,
                               drain_every=drain_every)

    def one_round_us(step):
        t0 = time.perf_counter()
        for _ in range(timed_steps):
            step(x, y)
        jax.block_until_ready(step.state.master_params[0])
        return (time.perf_counter() - t0) / timed_steps * 1e6

    def median(xs):
        s = sorted(xs)
        mid = len(s) // 2
        return s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2.0

    arms = [("base", build(False, 1))] + \
        [(de, build(True, de)) for de in drain_everys]
    for _, step in arms:        # warm every arm before any timing
        for _ in range(warmup):
            step(x, y)
        jax.block_until_ready(step.state.master_params[0])

    times = {name: [] for name, _ in arms}
    for _ in range(repeats):    # base + every arm inside each repeat
        for name, step in arms:
            times[name].append(one_round_us(step))

    base = times["base"]
    base_us = median(base)
    spread_pct = (max(base) - min(base)) / base_us * 100.0
    records = []
    for de in drain_everys:
        # paired per-repeat differences: contention in repeat r hits
        # both arms of r, so the median difference sheds it
        diff_us = median([t - b for t, b in zip(times[de], base)])
        t_us = base_us + diff_us
        records.append({
            "metric": "telemetry_overhead_us",
            "config": f"mlp_drain{de}", "drain_every": de,
            "platform": "cpu",
            "step_us_base": round(base_us, 1),
            "step_us_telemetry": round(t_us, 1),
            "telemetry_overhead_us": round(round(t_us, 1)
                                           - round(base_us, 1), 1),
            "overhead_pct": round(diff_us / base_us * 100.0, 2),
            "base_spread_pct": round(spread_pct, 2)})
    return records


def run_observe_microbench(args):
    stage("observe_microbench",
          "on-device telemetry carry overhead vs telemetry off, cpu")
    for rec in observe_microbench_records():
        emit(rec)
        register_record(rec)
    return 0


def overlap_microbench_records(ks=(1, 4, 16), dim=256, micro_batch=8,
                               warmup=2, timed_windows=6, n_batches=None):
    """``window_step_us`` microbench: the executor's two overlap knobs —
    ZeRO all-gather prefetch and async H2D double-buffering — each timed
    with overlap off vs on at K ∈ {1, 4, 16} microbatches per window.

    CPU-forced like the other microbenches.  Both arms of each knob
    compile the *same math DAG* (the gather arm is pinned bitwise by
    ``tests/test_executor.py``); the knob only moves where the gather /
    transfer is issued, so ``*_overlap_factor`` (off time / on time) is
    ~1.0 on CPU, where XLA runs collectives synchronously and the
    prefetcher's depth-2 queue has no async dispatch to hide under.  The
    record schema is the contract: multichip rounds replay this stage on
    the TPU backend and the factors become the overlap win.
    """
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.runtime import executor as rex
    from apex_tpu.training import make_train_step

    rng = np.random.default_rng(0)

    def build_zero(k):
        nn.manual_seed(0)
        model = nn.Sequential(nn.Linear(dim, dim), nn.ReLU(),
                              nn.Linear(dim, 10))
        opt = FusedSGD(list(model.parameters()), lr=0.1, momentum=0.9)
        return make_train_step(model, opt,
                               lambda o, t: F.cross_entropy(o, t),
                               grad_accum_steps=k, zero_stage=1,
                               zero_sharding=True, donate_state=False)

    def build_fused(k):
        nn.manual_seed(0)
        model = nn.Sequential(nn.Linear(dim, dim), nn.ReLU(),
                              nn.Linear(dim, 10))
        opt = FusedSGD(list(model.parameters()), lr=0.1, momentum=0.9)
        return make_train_step(model, opt,
                               lambda o, t: F.cross_entropy(o, t),
                               accum_steps=k, accum_stacked=(k > 1))

    def time_gather_us(k, on):
        rex.set_overlap(gather=on)
        try:
            step = build_zero(k)
            x = jnp.asarray(
                rng.standard_normal((micro_batch * k, dim)), jnp.float32)
            y = jnp.asarray(rng.integers(0, 10, (micro_batch * k,)))
            for _ in range(warmup):
                step(x, y)
            jax.block_until_ready(step.state.master_params[0])
            t0 = time.perf_counter()
            for _ in range(timed_windows):
                step(x, y)
            jax.block_until_ready(step.state.master_params[0])
            return (time.perf_counter() - t0) / timed_windows * 1e6
        finally:
            rex.set_overlap(gather="auto")

    def time_h2d_us(k, on):
        rex.set_overlap(h2d=on)
        try:
            step = build_fused(k)
            nb = n_batches if n_batches is not None \
                else k * (warmup + timed_windows)
            batches = [
                (rng.standard_normal((micro_batch, dim)).astype(np.float32),
                 rng.integers(0, 10, (micro_batch,)))
                for _ in range(nb)]
            kw = {"accum_steps": k} if k > 1 else {}
            rex.executor.drive(step, batches[:k * warmup], **dict(kw))
            jax.block_until_ready(step.state.master_params[0])
            t0 = time.perf_counter()
            losses = rex.executor.drive(step, batches[k * warmup:],
                                        **dict(kw))
            jax.block_until_ready(step.state.master_params[0])
            return (time.perf_counter() - t0) / max(len(losses), 1) * 1e6
        finally:
            rex.set_overlap(h2d="auto")

    records = []
    for k in ks:
        g_off = time_gather_us(k, False)
        g_on = time_gather_us(k, True)
        h_off = time_h2d_us(k, False)
        h_on = time_h2d_us(k, True)
        records.append({
            "metric": "window_step_us", "config": f"overlap_k{k}",
            "accum_steps": k, "micro_batch": micro_batch,
            "platform": "cpu",
            "window_step_us": round(g_on, 1),
            "gather_window_us_off": round(g_off, 1),
            "gather_window_us_on": round(g_on, 1),
            "gather_overlap_factor": round(g_off / g_on, 3),
            "h2d_window_us_off": round(h_off, 1),
            "h2d_window_us_on": round(h_on, 1),
            "h2d_overlap_factor": round(h_off / h_on, 3)})
    return records


def run_overlap_microbench(args):
    stage("overlap_microbench",
          "executor overlap knobs (gather prefetch, h2d double-buffer) "
          "off vs on, K in {1,4,16}, cpu")
    for rec in overlap_microbench_records():
        emit(rec)
        register_record(rec)
    return 0


def serve_bench_records(n_requests=200, seed=0, num_blocks=96,
                        block_size=8, max_batch=8, prefill_chunk=8,
                        arrival_rate=2.0, spec_k=3,
                        arms=("unified", "disaggregated", "speculative")):
    """``serve_throughput`` stage: the serving engine under a seeded
    Poisson open-loop trace of ``n_requests`` synthetic sessions
    (random prompt lengths / generation budgets, request i visible at
    its arrival tick whether or not the engine is keeping up — open
    loop, so queueing delay shows in the tail), one record per arm:

    * ``unified`` — one :class:`ServeEngine` time-slicing both phases
      (the PR 12 baseline record; its fields are a superset of the old
      single-record schema).
    * ``disaggregated`` — prefill engine + decode engine joined by the
      schema-3 streamed KV handoff
      (:class:`~apex_tpu.serve.DisaggregatedEngine`);
      ``handoff_bytes_peak_host`` is the largest single block buffer
      the handoff ever held on the host — the "KV never round-trips
      through one host" claim, measured.
    * ``speculative`` — disaggregated + batched speculative decoding
      on the decode engine: an int8-cached SELF-draft
      (:func:`~apex_tpu.inference.make_self_draft`), so acceptance is
      full and ``spec_tokens_per_tick`` isolates the verify
      machinery's committed tokens/tick (the >= 2 floor the tier-1
      schema test pins) from draft quality.

    CPU-forced like the microbenches; the model is the parity-test
    tiny GPT, so the numbers track the ENGINE (packing, paged gather/
    scatter, admission, handoff, verify) rather than CPU matmul
    throughput.  Every arm re-checks the serving engine's load-bearing
    claim: decode-path compiles after the whole trace stay within
    ``bucket_bound`` — the bucket grid — because bucketed operand
    shapes are the only decode shapes that exist (SERVE-SHAPE's
    invariant, measured; ragged acceptance included)."""
    import shutil
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.inference import make_self_draft
    from apex_tpu.models.gpt import GptModel
    from apex_tpu.observe import registry as obs
    from apex_tpu.runtime import step_cache as sc
    from apex_tpu.serve import (DisaggregatedEngine, Request,
                                ServeEngine, blocks_for, bucket)

    rng = np.random.default_rng(seed)
    nn.manual_seed(seed)
    model = GptModel(vocab_size=73, hidden=32, layers=2, heads=4,
                     max_positions=96, dropout=0.0, attn_dropout=0.0)
    model.eval()

    lens = rng.integers(2, 17, n_requests)
    news = rng.integers(2, 9, n_requests)
    reqs = [Request(f"s{i}",
                    [int(t) for t in rng.integers(1, 72, int(l))], int(m))
            for i, (l, m) in enumerate(zip(lens, news))]
    arrivals = np.cumsum(rng.poisson(arrival_rate, n_requests)).tolist()

    reg = obs.get_registry()
    # every decode shape the bucket tables can produce: batch buckets x
    # table buckets (the worst-case table covers the longest request
    # plus one block of growth headroom; speculative tables add spec_k
    # rows of verify headroom and the draft table bucket dimension)
    max_table = blocks_for(int(lens.max()) + int(news.max()),
                           block_size) + 1
    max_table_sp = blocks_for(int(lens.max()) + int(news.max()) + spec_k,
                              block_size) + 1
    n_batch_buckets = len({bucket(b, max_batch)
                           for b in range(1, max_batch + 1)})
    n_table_buckets = len({bucket(t) for t in range(1, max_table + 1)})
    n_table_buckets_sp = len({bucket(t)
                              for t in range(1, max_table_sp + 1)})

    records = []
    for arm in arms:
        stage("serve", f"arm {arm}")
        reg.clear_events()
        sc.reset_stats()
        sc.clear()
        preempt0 = int(obs.counter("serve.preemptions").value)
        tmp = None
        if arm == "unified":
            eng = ServeEngine(model, num_blocks=num_blocks,
                              block_size=block_size,
                              max_batch=max_batch,
                              prefill_chunk=prefill_chunk)
            pools = [eng.block_pool]
            decode_eng = eng
        else:
            tmp = tempfile.mkdtemp(prefix="apex_bench_handoff_")
            draft = make_self_draft(model) if arm == "speculative" \
                else None
            eng = DisaggregatedEngine(
                model, num_blocks=num_blocks, block_size=block_size,
                max_batch=max_batch, prefill_chunk=prefill_chunk,
                handoff_dir=tmp,
                decode_blocks=(2 * num_blocks if draft is not None
                               else num_blocks),
                draft=draft, spec_k=spec_k)
            pools = [eng.prefill.block_pool, eng.decode.block_pool]
            decode_eng = eng.decode
        peak_occ = 0.0
        i = 0
        t0 = time.perf_counter()
        while True:
            while i < n_requests and arrivals[i] <= eng.tick:
                eng.submit(reqs[i])
                i += 1
            more = eng.step()
            peak_occ = max([peak_occ] + [p.occupancy for p in pools])
            if not more and i >= n_requests:
                break
        wall_s = time.perf_counter() - t0
        for p in pools:
            p.check_no_leaks()
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)

        out = eng.results
        assert len(out) == n_requests
        total_tokens = sum(len(v) for v in out.values())
        ts = {(e["rid"], e["phase"]): e["ts_ms"]
              for e in reg.events("serve.request")}
        ttft = [ts[(r.rid, "first_token")] - ts[(r.rid, "queued")]
                for r in reqs]
        e2e = [ts[(r.rid, "done")] - ts[(r.rid, "queued")]
               for r in reqs]

        if arm == "speculative":
            decode_compiles = \
                int(sc.kind_stats("spec_verify_step")["compiles"]) \
                + int(sc.kind_stats("decode_step")["compiles"])
            # verify shapes: batch x target-table x draft-table buckets
            bucket_bound = (n_batch_buckets * n_table_buckets_sp
                            * n_table_buckets_sp)
        else:
            decode_compiles = \
                int(sc.kind_stats("decode_step")["compiles"])
            bucket_bound = n_batch_buckets * n_table_buckets

        rec = {
            "metric": "serve_throughput",
            "arm": arm,
            "config": f"gpt_tiny_poisson_n{n_requests}",
            "platform": "cpu",
            "requests": n_requests,
            "ticks": eng.tick,
            "tokens_per_s_per_chip": round(total_tokens / wall_s, 1),
            "p50_ms": round(float(np.percentile(e2e, 50)), 2),
            "p99_ms": round(float(np.percentile(e2e, 99)), 2),
            "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 2),
            "pool_occupancy": round(peak_occ, 3),
            "decode_compiles": decode_compiles,
            "bucket_bound": bucket_bound,
            "preemptions": int(obs.counter("serve.preemptions").value)
            - preempt0,
            "accept_rate": 0.0,
            "handoff_bytes_peak_host": 0,
        }
        if arm != "unified":
            h = eng.metrics()["handoff"]
            rec["handoff_bytes_peak_host"] = int(h["bytes_peak_host"])
            rec["handoffs"] = int(h["count"])
        if arm == "speculative":
            spec = decode_eng.metrics()["spec"]
            rec["accept_rate"] = round(float(spec["accept_rate"]), 4)
            # committed tokens per SEQUENCE per speculative tick — the
            # >= 2 tokens/tick acceptance floor is per sequence, so a
            # big batch can't fake it
            seq_ticks = spec["offered"] / spec_k if spec["offered"] \
                else 0
            rec["spec_tokens_per_tick"] = round(
                spec["committed_tokens"] / seq_ticks, 3) if seq_ticks \
                else 0.0
        records.append(rec)
    return records


def serve_prefix_bench_records(n_requests=24, seed=0, num_blocks=64,
                               block_size=8, max_batch=4,
                               prefill_chunk=40, shared_len=80,
                               arrival_gap=3):
    """``--serve`` shared-prefix arm: the prefix cache under the
    traffic shape it exists for — a Poisson open-loop trace where every
    request opens with the same ``shared_len``-token scaffold (a system
    prompt, block-aligned so full blocks are shareable) and most add a
    short unique suffix.  Every 4th request is EXACTLY the shared
    prompt, which is the full-chain-hit path: admission forks the last
    shared block copy-on-write before the first generated token can
    land in it.  Two records, ``cache_off`` then ``cache_on``, same
    trace, same model, so the deltas are the cache:

    * ``prefix_hit_rate`` — prompt tokens served from cache / prompt
      tokens submitted (>= 0.9 on this trace: only the first request
      pays the scaffold cold);
    * ``prefill_tokens_saved`` / ``cow_forks`` / ``cache_evictions`` —
      the engine's prefix-cache counters;
    * ``ttft_p50_ms`` — strictly better cache-on: warm requests prefill
      a 2-4 token suffix instead of the 80-token scaffold.

    The warm arm's outputs are asserted IDENTICAL to the cold arm's —
    the bitwise claim riding along in the bench, not just the tests."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models.gpt import GptModel
    from apex_tpu.observe import registry as obs
    from apex_tpu.runtime import step_cache as sc
    from apex_tpu.serve import Request, ServeEngine

    rng = np.random.default_rng(seed)
    nn.manual_seed(seed)
    model = GptModel(vocab_size=73, hidden=32, layers=2, heads=4,
                     max_positions=128, dropout=0.0, attn_dropout=0.0)
    model.eval()

    shared = [int(t) for t in rng.integers(1, 72, shared_len)]
    reqs = []
    for i in range(n_requests):
        if i % 4 == 0:
            prompt = list(shared)          # full-chain hit -> CoW fork
        else:
            suf = [int(t) for t in rng.integers(1, 72,
                                                int(rng.integers(2, 5)))]
            prompt = shared + suf
        reqs.append(Request(f"p{i}", prompt, int(rng.integers(2, 6))))
    arrivals = np.cumsum(rng.poisson(arrival_gap, n_requests)).tolist()

    reg = obs.get_registry()
    records = []
    outputs = {}
    for arm in ("cache_off", "cache_on"):
        stage("serve", f"shared-prefix arm {arm}")
        reg.clear_events()
        sc.reset_stats()
        sc.clear()
        eng = ServeEngine(model, num_blocks=num_blocks,
                          block_size=block_size, max_batch=max_batch,
                          prefill_chunk=prefill_chunk,
                          prefix_cache=(arm == "cache_on"))
        i = 0
        t0 = time.perf_counter()
        while True:
            while i < n_requests and arrivals[i] <= eng.tick:
                eng.submit(reqs[i])
                i += 1
            more = eng.step()
            if not more and i >= n_requests:
                break
        wall_s = time.perf_counter() - t0
        eng.block_pool.check_no_leaks()
        outputs[arm] = eng.results
        assert len(eng.results) == n_requests

        ts = {(e["rid"], e["phase"]): e["ts_ms"]
              for e in reg.events("serve.request")}
        ttft = [ts[(r.rid, "first_token")] - ts[(r.rid, "queued")]
                for r in reqs]
        pc = eng.metrics()["prefix_cache"]
        total_tokens = sum(len(v) for v in eng.results.values())
        records.append({
            "metric": "serve_prefix_cache",
            "arm": arm,
            "config": f"gpt_tiny_shared{shared_len}_n{n_requests}",
            "platform": "cpu",
            "requests": n_requests,
            "ticks": eng.tick,
            "tokens_per_s_per_chip": round(total_tokens / wall_s, 1),
            "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 3),
            "prefix_hit_rate": round(float(pc["hit_rate"]), 4),
            "prefill_tokens_saved": int(pc["prefill_tokens_saved"]),
            "cow_forks": int(pc["cow_forks"]),
            "cache_evictions": int(pc["cache_evictions"]),
            "cached_blocks": int(pc["cached_blocks"]),
            "decode_compiles": int(
                sc.kind_stats("decode_step")["compiles"]),
        })
    # same trace, same weights: the cache changes WHEN KV is computed,
    # never what it holds
    assert outputs["cache_on"] == outputs["cache_off"]
    return records


def run_serve(args):
    stage("serve",
          "continuous-batching paged-KV engine, 200-session Poisson "
          "open loop (unified / disaggregated / speculative), cpu")
    for rec in serve_bench_records():
        emit(rec)
        register_record(rec)
    stage("serve", "shared-prefix trace, prefix cache off vs on, cpu")
    for rec in serve_prefix_bench_records():
        emit(rec)
        register_record(rec)
    return 0


def serve_elastic_bench_records(n_requests=24, seed=0, n_engines=3,
                                num_blocks=48, block_size=8,
                                max_batch=4, prefill_chunk=4,
                                snapshot_every=2, miss_threshold=2):
    """``serve_elastic_recovery`` stage: the membership-backed
    :class:`~apex_tpu.serve.ServeFleet` through one full
    detect→shed→migrate→resume cycle — a replica hosting live
    sessions is chaos-felled mid-decode, the coordinator publishes
    the shrink epoch, batch-tier sessions are re-queued, latency-tier
    sessions restore from their committed snapshots into survivor
    pools, and every request still completes.  CPU-forced with
    SimClock + MemoryKV like the cluster bench, so ``detect_ms`` /
    ``migrate_ms`` measure the RUNTIME's bookkeeping (scan, manifest
    reads, block scatter), not accelerator speed.  One record."""
    import random
    import shutil
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")

    import apex_tpu.nn as nn
    from apex_tpu.models.gpt import GptModel
    from apex_tpu.runtime import chaos
    from apex_tpu.serve import Request, ServeFleet

    nn.manual_seed(6)
    model = GptModel(vocab_size=73, hidden=32, layers=2, heads=4,
                     max_positions=96, dropout=0.0,
                     attn_dropout=0.0).eval()
    rng = random.Random(seed)
    reqs = [Request(f"b{i}",
                    tuple(rng.randrange(1, 70)
                          for _ in range(rng.randrange(2, 10))),
                    rng.randrange(4, 12))
            for i in range(n_requests)]
    slos = [rng.choice(("latency", "batch")) for _ in range(n_requests)]

    def _kill(member_id):
        def act(ctx):
            if ctx.get("member") == member_id:
                raise chaos.ChaosKilled(f"bench: felled {member_id}")
        return act

    snap_root = tempfile.mkdtemp(prefix="apex_serve_elastic_bench_")
    try:
        with chaos.session(seed=seed) as c:
            # fell one replica once the fleet is warm: past the first
            # snapshot cadence, with sessions mid-decode everywhere
            kill_after = n_engines * (3 * snapshot_every + 2)
            c.on("host.loss", _kill("serve0"), after=kill_after,
                 times=-1)
            fleet = ServeFleet(
                model, n_engines=n_engines, num_blocks=num_blocks,
                block_size=block_size, max_batch=max_batch,
                prefill_chunk=prefill_chunk,
                snapshot_every=snapshot_every,
                miss_threshold=miss_threshold, snapshot_dir=snap_root)
            with fleet:
                fleet.join()
                results = fleet.run(reqs, slos=slos)
                m = fleet.metrics()
    finally:
        shutil.rmtree(snap_root, ignore_errors=True)

    if len(results) != n_requests:
        fail(f"serve_elastic_incomplete: {len(results)} of "
             f"{n_requests} requests completed across the shrink")
    return [{
        "metric": "serve_elastic_recovery",
        "platform": "cpu",
        "engines": n_engines,
        "requests": n_requests,
        "completed": len(results),
        "epoch": m["epoch"],
        "detect_ms": m["detect_ms"],
        "migrate_ms": m["migrate_ms"],
        "sessions_migrated": m["sessions_migrated"],
        "sessions_shed_requeued": m["sessions_shed_requeued"],
        "sessions_recomputed": m["sessions_recomputed"],
        "snapshot_bytes_peak_host": m["snapshot_bytes_peak_host"],
    }]


def run_serve_elastic(args):
    stage("serve_elastic",
          "membership-backed serve fleet through one "
          "detect→shed→migrate→resume cycle (chaos host loss "
          "mid-decode), cpu")
    for rec in serve_elastic_bench_records():
        emit(rec)
        register_record(rec)
    return 0


def rollout_bench_records(rounds=8, seed=0, num_blocks=64,
                          rollouts_per_round=4, train_steps_per_round=2,
                          publish_every=1):
    """``rollout_loop`` stage: the generate-then-train runtime
    (:class:`~apex_tpu.rollout.RolloutRuntime`) driven end to end —
    seeded prompt stream → speculative serve engine → bounded-staleness
    buffer → fused train step → measured weight publish back into the
    engine, with the online draft distiller riding the same rounds.
    CPU-forced with the parity-test tiny GPT, so the numbers track the
    LOOP (scheduling, buffer replay, reshard accounting, hot-swap),
    not matmul throughput.  One record:

    * ``rollout_tokens_per_s`` / ``train_steps_per_s`` — generated
      tokens and fused steps over the loop's wall clock (the loop is
      serial by construction, so one clock prices both sides);
    * ``weight_sync_ms`` — median over every ``rollout.weight_sync``
      event (target + draft publishes);
    * ``zero_copy_frac`` — the last target publish's per-leaf
      zero-copy hit fraction (1.0 on cpu: identical layouts, donation
      off, so the fast path aliases every leaf);
    * ``accept_rate_trend`` — acceptance measured under each outgoing
      draft, logged by the distiller at publish time (should climb as
      the draft distills against the live target);
    * ``buffer_staleness_p50`` — median over the per-round median
      sample ages, in weight epochs (the staleness bound, observed).
    """
    import statistics
    import time as _time

    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import apex_tpu.nn as nn
    import apex_tpu.nn.functional as F
    from apex_tpu.inference import make_self_draft
    from apex_tpu.models.gpt import GptModel
    from apex_tpu.observe import registry as obs
    from apex_tpu.optimizers.fused_adam import FusedAdam
    from apex_tpu.rollout import OnlineDistiller, RolloutRuntime
    from apex_tpu.serve import ServeEngine
    from apex_tpu.training.step import make_train_step

    V = 73
    nn.manual_seed(6)
    train_m = GptModel(vocab_size=V, hidden=32, layers=2, heads=4,
                       max_positions=96, dropout=0.0, attn_dropout=0.0)
    serve_m = make_self_draft(train_m)
    nn.manual_seed(99)
    draft_master = GptModel(vocab_size=V, hidden=32, layers=2, heads=4,
                            max_positions=96, dropout=0.0,
                            attn_dropout=0.0)

    def lm_loss(logits, ids):
        flat = logits[:, :-1].reshape((-1, V))
        return F.cross_entropy(flat, ids[:, 1:].reshape((-1,)))

    eng = ServeEngine(serve_m, num_blocks=num_blocks, block_size=8,
                      max_batch=4, prefill_chunk=4,
                      draft=make_self_draft(draft_master),
                      spec_k=4)
    step = make_train_step(
        train_m, FusedAdam(list(train_m.parameters()), lr=1e-3),
        lm_loss, loss_scale=1.0)
    rt = RolloutRuntime(
        eng, step, distiller=OnlineDistiller(eng, draft_master, lr=1e-3),
        rollouts_per_round=rollouts_per_round,
        train_steps_per_round=train_steps_per_round,
        publish_every=publish_every, prompt_len=6, max_new_tokens=6,
        seq_len=16, seed=seed)

    reg = obs.get_registry()
    reg.clear_events()
    # warmup round outside the clock: first round pays every serve /
    # train / distill / publish compile, which would otherwise dominate
    # the per-second rates at toy scale
    rt.run_round()
    tokens0, steps0 = rt.tokens_generated, len(rt.losses)
    t0 = _time.perf_counter()
    round_recs = rt.run(rounds)
    wall_s = _time.perf_counter() - t0

    sync_ms = [ev["weight_sync_ms"]
               for ev in reg.events("rollout.weight_sync")]
    p50s = [r["staleness_p50"] for r in round_recs
            if r["staleness_p50"] is not None]
    trend = [r["accept_rate"] for r in rt.distiller.publish_log
             if r["accept_rate"] is not None]
    rec = {
        "metric": "rollout_loop", "config": "toy_gpt_distill",
        "platform": "cpu", "rounds": rounds,
        "rollout_tokens_per_s": round(
            (rt.tokens_generated - tokens0) / wall_s, 1),
        "train_steps_per_s": round(
            (len(rt.losses) - steps0) / wall_s, 2),
        "weight_sync_ms": round(statistics.median(sync_ms), 3)
            if sync_ms else None,
        "zero_copy_frac": rt.publisher.last_stats.get("zero_copy_frac"),
        "accept_rate_trend": [round(float(r), 4) for r in trend],
        "buffer_staleness_p50": float(np.median(p50s)) if p50s else None,
        "weight_epoch": eng.weight_epochs["target"],
        "publishes": rt.publisher.publishes,
        "backpressure_rounds": rt.backpressure_rounds,
        "loss_first": round(rt.losses[0], 4),
        "loss_last": round(rt.losses[-1], 4),
    }
    eng.close()
    return [rec]


def run_rollout(args):
    stage("rollout",
          "generate-then-train loop: seeded prompts → spec serve → "
          "staleness-bounded buffer → fused step → measured weight "
          "publish (+ online draft distillation), cpu")
    # the loop crosses the serve engine, the executor, and the reshard
    # surface in one process — bound it like the backend probes
    recs = _run_with_timeout(
        rollout_bench_records, args.budget_s,
        "rollout_unresponsive: the generate-then-train loop did not "
        f"complete within {args.budget_s}s — a serve dispatch or "
        "publish is likely stuck")
    for rec in recs:
        emit(rec)
        register_record(rec)
    return 0


def ckpt_microbench_records(total_mb=64, n_tensors=32, repeats=3,
                            directory=None):
    """``ckpt_save_ms`` microbench: CheckpointManager sync save vs async
    save (submit latency + drain), plus how much host "training" work the
    async path overlaps.  CPU-forced like the opt microbench — the
    quantity under test is host serialization + IO, which no accelerator
    touches.  Returns JSON-able records.
    """
    import shutil
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.runtime.resilience import CheckpointManager

    per = int(total_mb * 1e6 / 4 / n_tensors)
    rng = np.random.default_rng(0)
    state = {f"w{i}": jnp.asarray(rng.standard_normal(per), jnp.float32)
             for i in range(n_tensors)}
    jax.block_until_ready(state["w0"])

    # the overlap probe: a host workload sized to ~one sync save
    probe = np.ascontiguousarray(rng.standard_normal(per))

    def host_work(n):
        acc = 0.0
        for _ in range(n):
            acc += float(probe.sum())
        return acc

    base = directory or tempfile.mkdtemp(prefix="apex_tpu_ckpt_bench_")
    records = []
    try:
        mgr = CheckpointManager(os.path.join(base, "sync"), keep_n=2)
        times = []
        for r in range(repeats):
            t0 = time.perf_counter()
            mgr.save(r, model=state)
            times.append((time.perf_counter() - t0) * 1e3)
        sync_ms = min(times)
        records.append({"metric": "ckpt_save_ms", "mode": "sync",
                        "mb": total_mb, "tensors": n_tensors,
                        "platform": "cpu", "value": round(sync_ms, 2)})

        mgr = CheckpointManager(os.path.join(base, "async"), keep_n=2)
        submit, drain = [], []
        for r in range(repeats):
            t0 = time.perf_counter()
            h = mgr.save_async(r, model=state)
            submit.append((time.perf_counter() - t0) * 1e3)
            # overlapped host work while the writer thread pickles+writes
            work_units = 8
            t1 = time.perf_counter()
            host_work(work_units)
            work_s = time.perf_counter() - t1
            t2 = time.perf_counter()
            h.wait()
            drain.append((time.perf_counter() - t2) * 1e3)
        mgr.close()
        records.append({"metric": "ckpt_save_ms", "mode": "async_submit",
                        "mb": total_mb, "tensors": n_tensors,
                        "platform": "cpu", "value": round(min(submit), 2),
                        "note": "device->host transfer on caller thread"})
        records.append({"metric": "ckpt_save_ms", "mode": "async_drain",
                        "mb": total_mb, "tensors": n_tensors,
                        "platform": "cpu", "value": round(min(drain), 2),
                        "overlapped_host_work_ms": round(work_s * 1e3, 2),
                        "note": "wait() after overlapped host work"})
        records.append({
            "metric": "ckpt_save_overlap_x",
            "mb": total_mb, "platform": "cpu",
            "value": round(sync_ms / max(min(submit) + min(drain), 1e-3), 3),
            "unit": "x_sync_blocking_over_async_critical_path"})
    finally:
        if directory is None:
            shutil.rmtree(base, ignore_errors=True)
    return records


def run_ckpt_microbench(args):
    stage("ckpt_microbench", "CheckpointManager sync vs async, cpu")
    for rec in ckpt_microbench_records():
        emit(rec)
        register_record(rec)
    return 0


def elastic_bench_records(dim=32, batch=8, pre_steps=3, lost_steps=2,
                          directory=None):
    """``--elastic``: the preempt→shrink→replan→reshard→resume cycle on
    the host mesh, timed.  CPU-forced like the ckpt microbench — the
    quantities under test (planner latency, host-side reshard, resume
    gap) touch no accelerator math.  One record per topology transition
    (shrink to half the devices, then regrow to all of them), each
    carrying ``{replan_ms, reshard_ms, resume_gap_steps}``.
    """
    import shutil
    import tempfile

    # standalone runs need the 8-virtual-device host mesh or the shrink
    # transition degenerates to 1→1; only effective before the backend
    # initializes (under pytest, conftest.py already forced it)
    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.parallel import auto
    from apex_tpu.runtime import CheckpointManager, chaos
    from apex_tpu.runtime.elastic import ElasticTrainer

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, dim)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, (batch,)))

    nn.manual_seed(0)
    model = nn.Sequential(nn.Linear(dim, dim), nn.ReLU(),
                          nn.Linear(dim, 10))
    opt = FusedSGD(list(model.parameters()), lr=0.1, momentum=0.9)

    def rec(event, from_n, trainer, steps_done, next_step):
        t = trainer.telemetry
        saved = trainer.manager.restore(
            trainer.resume_step, return_manifest=True)[1] or {}
        saved_plan = saved.get("plan")
        return {"metric": "elastic_recovery", "event": event,
                "platform": "cpu",
                "from_devices": from_n, "to_devices": t["n_devices"],
                "plan": t["plan"],
                "ckpt_plan": (auto.plan_from_key(
                    saved_plan["key"], saved_plan["n_devices"]).name()
                    if saved_plan else None),
                "replan_ms": t["replan_ms"],
                "reshard_ms": t["reshard_ms"],
                "resume_gap_steps": int(steps_done - next_step)}

    base = directory or tempfile.mkdtemp(prefix="apex_tpu_elastic_bench_")
    records = []
    try:
        mgr = CheckpointManager(os.path.join(base, "ckpts"), keep_n=2)
        trainer = ElasticTrainer(
            mgr, model, opt, lambda o, t: F.cross_entropy(o, t),
            example_batch=(x, y), half_dtype=None, loss_scale=1.0,
            plan_filter=lambda p: p.dp == p.n_devices and p.accum == 1)
        n_full = len(jax.devices())
        trainer.restore()
        for _ in range(pre_steps):
            trainer(x, y)
        trainer.save(pre_steps - 1)
        for _ in range(lost_steps):     # un-checkpointed: the resume gap
            trainer(x, y)
        done = pre_steps + lost_steps

        # preemption: the slice comes back at half size
        half = max(1, n_full // 2)
        with chaos.session(seed=0) as c:
            c.on("device.loss", action=lambda ctx: half, at=0)
            next_step = trainer.restore()
        records.append(rec("shrink", n_full, trainer, done, next_step))

        trainer(x, y)                   # one step on the small mesh
        trainer.save(next_step)
        done = next_step + 1
        next_step = trainer.restore()   # regrow: full mesh is back
        records.append(rec("regrow", half, trainer, done, next_step))
    finally:
        if directory is None:
            shutil.rmtree(base, ignore_errors=True)
    return records


def run_elastic(args):
    stage("elastic", "preempt→shrink→replan→reshard→resume cycle, cpu")
    for r in elastic_bench_records():
        emit(r)
        register_record(r)
    return 0


def cluster_bench_records(dim=32, batch=24, n_hosts=4, pre_steps=3,
                          directory=None, spawn_processes=True):
    """``--cluster``: the multi-host elastic cycle on the CPU host mesh.

    Runs the full detect→agree→replan→reshard cycle in-process (the
    tier-1 simulation: ``n_hosts`` heartbeat agents over a shared
    MemoryKV and a fake clock, one host felled by chaos) and emits one
    ``cluster_recovery`` record with ``{membership_epochs, detect_ms,
    replan_ms, stream_restore_ms, gathered_restore_ms,
    shard_bytes_peak_host, gathered_state_bytes}`` — the streamed-vs-
    gathered pair is the streaming-shard-IO claim: the streamed restore's
    host high-water mark stays below the gathered full-state size.

    With ``spawn_processes`` a second ``cluster_process_detect`` record
    crosses REAL process boundaries: child OS processes heartbeat over a
    FileKV until their beats run out, and the parent coordinator times
    admission and loss detection.  CPU-forced like the elastic stage —
    nothing here touches accelerator math.
    """
    import shutil
    import tempfile

    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=8")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.cluster import (ClusterTrainer, Coordinator, FileKV,
                                  current_epoch, spawn_member_process)
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.runtime import chaos, resilience
    from apex_tpu.runtime import executor as _executor
    from apex_tpu.training import make_train_step

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, dim)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, (batch,)))

    def mk(seed=0):
        nn.manual_seed(seed)
        model = nn.Sequential(nn.Linear(dim, dim), nn.ReLU(),
                              nn.Linear(dim, 10))
        return model, FusedSGD(list(model.parameters()), lr=0.1,
                               momentum=0.9)

    base = directory or tempfile.mkdtemp(prefix="apex_tpu_cluster_bench_")
    records = []
    try:
        model, opt = mk()
        ct = ClusterTrainer(
            os.path.join(base, "ckpts"), model, opt,
            lambda o, t: F.cross_entropy(o, t), example_batch=(x, y),
            n_hosts=n_hosts, half_dtype=None, loss_scale=1.0,
            plan_filter=lambda p: p.dp == p.n_devices and p.accum == 1
            and p.zero_stage == 0 and not p.chunked_loss)
        ct.join()
        ct.recover()
        for _ in range(pre_steps):
            ct(x, y)
        ct.save(pre_steps - 1)
        save_peak = ct.trainer.manager.last_save_stats.get(
            "shard_bytes_peak_host", 0)

        # one host's process dies; two stale scans fell it
        victim = ct.hosts[-1].member_id

        def kill(ctx):
            if ctx.get("member") == victim:
                raise chaos.ChaosKilled(f"{victim} died")

        t0 = time.perf_counter()
        with chaos.session(seed=0) as c:
            c.on("host.loss", action=kill, times=-1)
            ct.tick(ct.deadline_s * 1.2)
            ct.tick(ct.deadline_s * 1.2)
        detect_ms = (time.perf_counter() - t0) * 1e3
        ct.recover()
        tel = ct.telemetry
        ct(x, y)                        # one resumed step on the survivors

        # the gathered arm: assemble the full host state and reshard it
        # into a fresh step under the SAME surviving-fleet plan
        step_no = ct.trainer.resume_step
        mgr = ct.trainer.manager
        t0 = time.perf_counter()
        host = resilience.read_checkpoint_file(mgr.path_for(step_no))
        model2, opt2 = mk(seed=1)
        fresh = make_train_step(
            model2, opt2, lambda o, t: F.cross_entropy(o, t),
            half_dtype=None, loss_scale=1.0, parallel=ct.plan,
            devices=ct.trainer.devices)
        fresh.state = resilience.reshard_state(host["state"], fresh.state)
        gathered_ms = (time.perf_counter() - t0) * 1e3
        gathered_bytes = sum(
            a.nbytes for a in jax.tree_util.tree_leaves(host["state"])
            if isinstance(a, np.ndarray))

        records.append({
            "metric": "cluster_recovery", "platform": "cpu",
            "hosts": n_hosts, "membership_epochs": current_epoch(ct.kv),
            "surviving_devices": tel["n_devices"], "plan": ct.plan.name(),
            "detect_ms": round(detect_ms, 3),
            "replan_ms": tel["replan_ms"],
            "stream_restore_ms": tel["reshard_ms"],
            "gathered_restore_ms": round(gathered_ms, 3),
            "shard_bytes_peak_host": tel["restore_peak_host_bytes"],
            "gathered_state_bytes": int(gathered_bytes),
            "shard_bytes_peak_save": save_peak,
            "restore_mode": tel["restore_mode"]})
        _executor.set_cluster_epoch(None)

        if spawn_processes:
            kv_dir = os.path.join(base, "kv")
            kv = FileKV(kv_dir)
            procs = [spawn_member_process(kv_dir, f"proc{i}",
                                          interval_s=0.05, beats=40)
                     for i in range(2)]
            coord = Coordinator(kv, deadline_s=1.0, miss_threshold=2)
            t0 = time.perf_counter()
            admitted = None
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                view = coord.scan()
                if len(view.members) == len(procs):
                    admitted = (time.perf_counter() - t0) * 1e3
                    break
                time.sleep(0.1)
            for p in procs:
                p.wait(timeout=60.0)
            t0 = time.perf_counter()
            lost = None
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if not coord.scan().members:
                    lost = (time.perf_counter() - t0) * 1e3
                    break
                time.sleep(0.2)
            records.append({
                "metric": "cluster_process_detect", "platform": "cpu",
                "processes": len(procs), "kv": "file",
                "admit_ms": round(admitted, 1) if admitted else None,
                "loss_detect_ms": round(lost, 1) if lost else None,
                "epochs": current_epoch(kv)})
    finally:
        if directory is None:
            shutil.rmtree(base, ignore_errors=True)
    return records


def run_cluster(args):
    stage("cluster", "multi-host detect→agree→replan→reshard cycle, cpu")
    for r in cluster_bench_records():
        emit(r)
        register_record(r)
    return 0


def plan_bench_records(vocab=2048, hidden=192, layers=4, heads=6, seq=128,
                       batch=16, topk=3, timed_steps=3):
    """``--plan``: the parallelism planner's predicted-vs-measured
    calibration loop on the current chip.

    Plans a GPT-shaped LM config with the analytical cost model, then
    compiles and times the top-k feasible plans through the real step
    (the ``auto_tune`` machinery) and emits one record per plan with
    both numbers — the correlation is what validates the CHIPS constants
    for this backend.  Returns JSON-able records.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.models import GptModel
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import auto

    nn.manual_seed(0)
    model = GptModel(vocab_size=vocab, hidden=hidden, layers=layers,
                     heads=heads, max_positions=seq, dropout=0.0,
                     attn_dropout=0.0)
    opt = FusedAdam(list(model.parameters()), lr=1e-3)

    def lm_loss(logits, tgt):
        return F.cross_entropy(logits.reshape((-1, vocab)),
                               tgt.reshape((-1,)))

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq)))
    tgt = jnp.asarray(np.roll(np.asarray(ids), -1, axis=1))

    stage("plan_enumerate", f"gpt {layers}L/{hidden}H vocab {vocab} "
                            f"batch {batch} seq {seq}")
    report = auto.plan_training(model, opt, lm_loss, (ids, tgt))
    spec = report.chip
    records = []
    stage("plan_measure", f"top-{topk} of {len(report.ranked)} feasible")
    for rank, plan in enumerate(report.ranked[:topk]):
        try:
            nn.manual_seed(0)
            m = GptModel(vocab_size=vocab, hidden=hidden, layers=layers,
                         heads=heads, max_positions=seq, dropout=0.0,
                         attn_dropout=0.0)
            o = FusedAdam(list(m.parameters()), lr=1e-3)
            measured = auto.measure_plan(
                plan, m, o, lm_loss, (ids, tgt), steps=timed_steps,
                half_dtype=None, loss_scale=1.0)
            err = None
        except Exception as e:          # a plan that fails to run reports so
            measured, err = None, f"{type(e).__name__}: {e}"
        rec = {"metric": "plan_predicted_vs_measured_ms",
               "chip": spec.name, "rank": rank, "plan": plan.name(),
               "predicted_ms": round(plan.predicted_ms, 3),
               "predicted_hbm_mb":
                   round(plan.predicted_hbm / 2 ** 20, 2),
               "measured_ms": (round(measured, 3)
                               if measured is not None else None),
               "rel_err": (round(plan.predicted_ms / measured - 1.0, 3)
                           if measured else None)}
        if err:
            rec["error"] = err
        records.append(rec)
    records.append({
        "metric": "plan_report", "chip": spec.name,
        "chosen": report.best.name(), "feasible": len(report.ranked),
        "rejected": len(report.rejected),
        "rejected_reasons": sorted({r.split(":")[0]
                                    for _, r in report.rejected})})
    records.append(_plan_search_record("gpt", report, topk))

    # switch-MoE profile: the same LM with every other FFN a 4-expert
    # switch block.  Planned against a v5e:4 fleet so the ep=4 twin is
    # in the space (CPU has one device); search telemetry only — ep
    # plans need the real axis to run.
    stage("plan_search_moe", "switch-MoE twin (4 experts over v5e:4)")
    try:
        nn.manual_seed(0)
        moe = GptModel(vocab_size=vocab, hidden=hidden, layers=layers,
                       heads=heads, max_positions=seq, dropout=0.0,
                       attn_dropout=0.0, moe_axis="data",
                       moe_num_experts=4, moe_every=min(2, layers))
        moe_opt = FusedAdam(list(moe.parameters()), lr=1e-3)
        moe_report = auto.plan_training(moe, moe_opt, lm_loss,
                                        (ids, tgt), fleet="v5e:4")
        records.append(_plan_search_record("switch_moe", moe_report,
                                           topk))
    except Exception as e:      # a broken MoE search is a record,
        records.append({        # not a dead bench run
            "metric": "plan_search", "profile": "switch_moe",
            "error": f"{type(e).__name__}: {e}"})
    return records


def _plan_search_record(profile_name, report, topk):
    """One ``plan_search`` record: the joint-search telemetry the
    observe catalog names (plan.search_ms / explored / pruned_oom) plus
    predicted-vs-chosen for the top-k feasible plans."""
    best_ms = report.best.predicted_ms if report.best else None
    top = [{"plan": p.name(),
            "predicted_ms": round(p.predicted_ms, 3),
            "vs_chosen_ms": round(p.predicted_ms - best_ms, 3)}
           for p in report.ranked[:topk]]
    return {"metric": "plan_search", "profile": profile_name,
            "chip": report.chip.name,
            "plans_explored": report.explored,
            "plans_pruned_oom": report.pruned_oom,
            "search_ms": round(report.search_ms, 3),
            "chosen": report.best.name() if report.best else None,
            "top": top}


def run_plan_bench(args):
    stage("plan_bench", "analytical planner predicted-vs-measured")
    try:
        init_backend()
    except Exception as e:
        fail(f"backend_init_failed: {type(e).__name__}: {e}")
        return 1
    for rec in plan_bench_records(batch=args.batch or 16):
        emit(rec)
    return 0


def lint_records():
    """``--lint``: analyzer health alongside the perf metrics.

    Runs the full apex_tpu.lint rule set (docs/lint.md) over the package
    and the examples — the same scope as the tier-1 gate
    (tests/test_lint_clean.py) — so a multichip bench round also records
    whether the tree it measured was hazard-clean, and how much the
    analyzer itself costs.  The AST pass needs no backend; the jaxpr
    audit traces the entry programs on CPU, so neither needs a chip.
    """
    from apex_tpu import lint as tpu_lint
    from apex_tpu.lint import jaxpr_audit

    repo = os.path.dirname(os.path.abspath(__file__))
    targets = [p for p in (os.path.join(repo, "apex_tpu"),
                           os.path.join(repo, "examples"))
               if os.path.isdir(p)]
    res = tpu_lint.run(targets, root=repo)
    c = res.counts()
    audit = jaxpr_audit.run()
    a = audit.counts()
    return [{
        "metric": "lint_findings",
        "value": c["findings"], "unit": "findings",
        "lint_findings": c["findings"],
        "lint_ms": c["lint_ms"],
        "dataflow_ms": c["dataflow_ms"],
        "stale_suppressions": c["stale_suppressions"],
        "rules_run": c["rules_run"],
        "files_scanned": c["files"],
        "suppressed": c["suppressed"],
        "baselined": c["baselined"],
        "jaxpr_audit_ms": a["jaxpr_audit_ms"],
        "programs_audited": a["programs_audited"],
        "jaxpr_failures": a["failures"],
    }]


def run_lint(args):
    stage("lint", "apex_tpu + examples, full rule set")
    for rec in lint_records():
        emit(rec)
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("batch", nargs="?", type=int, default=None)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--kernels", action="store_true",
                    help="run only the Pallas kernel parity checks")
    ap.add_argument("--profile", action="store_true",
                    help="measured per-op-family time attribution of one "
                         "step via the pyprof trace pipeline (pair with "
                         "--gpt/--bert for those configs)")
    ap.add_argument("--kernels-timing", action="store_true",
                    help="A/B-time Pallas kernels vs their plain-XLA "
                         "fallbacks (meaningful on real TPU)")
    ap.add_argument("--bert", action="store_true",
                    help="run the BERT-base pretrain config (BASELINE.md 4) "
                         "instead of ResNet-50")
    ap.add_argument("--llama", action="store_true",
                    help="Llama-style ~125M causal LM (RoPE/RMSNorm/"
                         "SwiGLU/GQA) FusedAdam throughput")
    ap.add_argument("--gpt", action="store_true",
                    help="run the GPT-2-small causal-LM config")
    ap.add_argument("--llama-decode", action="store_true",
                    help="greedy KV-cache decode tokens/s on the "
                         "llama_125m GQA geometry; --window N adds the "
                         "Mistral band + rolling cache arm")
    ap.add_argument("--window", type=int, default=None,
                    help="sliding_window for --llama-decode (rolling "
                         "cache: O(window) cache reads per token)")
    ap.add_argument("--gpt-decode", action="store_true",
                    help="measure greedy KV-cache decode tokens/s")
    ap.add_argument("--int8", action="store_true",
                    help="with --gpt-decode: weight-only int8 "
                         "quantization (w8a16) before decoding")
    ap.add_argument("--kv-int8", action="store_true",
                    help="with --gpt-decode: int8 KV cache "
                         "(cache_dtype='int8') — the long-context "
                         "cache-traffic lever")
    ap.add_argument("--spec-decode", action="store_true",
                    help="speculative vs plain greedy decode on the "
                         "llama config (draft-verified, output exact)")
    ap.add_argument("--seq2seq", action="store_true",
                    help="run the transformer-base seq2seq config")
    ap.add_argument("--vit", action="store_true",
                    help="ViT-S/16 at 224 classification throughput")
    ap.add_argument("--dcgan", action="store_true",
                    help="DCGAN 64x64 multi-model/multi-loss amp "
                         "iteration (BASELINE config 5)")
    ap.add_argument("--nhwc", action="store_true",
                    help="channels-last (NHWC) arm of the resnet config "
                         "(nn.to_channels_last): the conv-layout MFU "
                         "lever — A/B against the default NCHW run")
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--gpt-size", default="small",
                    choices=["small", "medium"],
                    help="with --gpt: GPT-2 geometry")
    ap.add_argument("--pad-vocab", action="store_true",
                    help="lane-pad the GPT vocab to a multiple of 128 "
                         "(Megatron make-vocab-size-divisible-by; exact "
                         "numerics via -1e30-masked pad columns)")
    ap.add_argument("--attn-dropout", type=float, default=0.0,
                    help="attention-probs dropout rate for the --gpt and "
                         "--bert configs (default 0: the stable headline "
                         "configs; 0.1 = the historical recipes, riding "
                         "the in-kernel hash-mask dropout)")
    ap.add_argument("--remat", action="store_true",
                    help="with --gpt: rematerialize block activations "
                         "(long-sequence configs)")
    ap.add_argument("--sweep", type=str, default=None,
                    help="comma-separated batch list, e.g. 64,128,256: "
                         "one JSON line per batch in one warm process "
                         "(find the throughput/MFU sweet spot)")
    ap.add_argument("--plain-loss", action="store_true",
                    help="LM configs: plain log-softmax cross-entropy "
                         "instead of the fused lse-residual xentropy "
                         "(A/B the backward-memory win)")
    ap.add_argument("--loss-mode", default=None,
                    choices=["fused", "plain", "chunked", "kernel"],
                    help="--gpt/--llama vocab-chain implementation "
                         "(VERDICT r4 #1 in-step A/B): fused = "
                         "materialized logits + contrib xentropy "
                         "(round-4 default); chunked = head+loss per "
                         "row-chunk under jax.checkpoint, (N,V) never "
                         "materializes; kernel = the Pallas fused "
                         "lm-head+loss kernel wired into the step")
    ap.add_argument("--chunk-rows", type=int, default=None,
                    help="--loss-mode chunked: rows per chunk "
                         "(default auto ~64M logits elements)")
    ap.add_argument("--full-mlm-head", action="store_true",
                    help="--bert: run the MLM head over ALL positions "
                         "(the pre-round-5 path) instead of the "
                         "reference recipe's masked_lm_positions "
                         "gather — the A/B arm")
    ap.add_argument("--draft", default="trained",
                    choices=["trained", "random"],
                    help="--spec-decode: draft quality — 'trained' "
                         "trains target+draft at bench time on a "
                         "deterministic successor task (real "
                         "acceptance), 'random' is the overhead-floor "
                         "arm (acceptance ~0)")
    ap.add_argument("--draft-steps", type=int, default=400,
                    help="--spec-decode --draft trained: draft train "
                         "steps (fewer = lower acceptance operating "
                         "point)")
    ap.add_argument("--dynamic-scale", action="store_true",
                    help="--gpt: run the step with loss_scale='dynamic' "
                         "(full fp16-style unscale + overflow-check + "
                         "skip machinery) instead of the bf16 1.0 fast "
                         "path — prices the reference's signature "
                         "scaler on-chip")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="--gpt/--llama: microbatch the step K ways "
                         "inside one compiled program (lax.scan grad "
                         "accumulation) — the program-level pipelining "
                         "arm of the vocab-chain A/B")
    ap.add_argument("--flat-optim", action="store_true",
                    help="resnet config: the flat_master shape-bucketed "
                         "optimizer-state A/B arm — measured LOSING on "
                         "v5e (2256 vs 2355 img/s; unledgered run, round 5), "
                         "kept as the reference multi_tensor_apply "
                         "design's receipt")
    ap.add_argument("--no-kernels", action="store_true",
                    help="skip the kernel parity checks")
    ap.add_argument("--opt-microbench", action="store_true",
                    help="opt_step_us stage: FusedAdam eager-step "
                         "microbench (step cache vs pre-cache per-bucket "
                         "dispatch) at 1M/10M params, forced onto the CPU "
                         "backend (it measures host dispatch)")
    ap.add_argument("--accum-microbench", action="store_true",
                    help="accum_step_us stage: the one-executable "
                         "gradient-accumulation window at K in {1,4,16} "
                         "(make_train_step(accum_steps=K)); reports "
                         "dispatches-per-window from step_cache.stats() "
                         "— pinned at 1 for every K — CPU-forced like "
                         "--opt-microbench")
    ap.add_argument("--plan", action="store_true",
                    help="plan_predicted_vs_measured_ms stage: run the "
                         "analytical parallelism planner "
                         "(apex_tpu.parallel.auto) on a GPT-shaped LM "
                         "config for the current chip, then compile+time "
                         "its top-3 plans and emit predicted-vs-measured "
                         "per plan — the CHIPS constants calibration "
                         "loop (docs/auto_parallel.md)")
    ap.add_argument("--lint", action="store_true",
                    help="lint_findings stage: run the apex_tpu.lint "
                         "TPU-hazard analyzer (docs/lint.md) over "
                         "apex_tpu/ and examples/ and emit "
                         "{lint_findings, lint_ms, rules_run} — records "
                         "analyzer health alongside perf; pure-AST, no "
                         "backend needed")
    ap.add_argument("--ckpt-microbench", action="store_true",
                    help="ckpt_save_ms stage: CheckpointManager sync vs "
                         "async save (submit/drain split + overlap factor) "
                         "on a 64MB state, CPU-forced — tracks checkpoint "
                         "overhead next to the training metrics")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic_recovery stage: the preempt→shrink→"
                         "replan→reshard→resume cycle on the CPU host "
                         "mesh, emitting {replan_ms, reshard_ms, "
                         "resume_gap_steps} per topology transition")
    ap.add_argument("--cluster", action="store_true",
                    help="cluster_recovery stage: the multi-host "
                         "detect→agree→replan→reshard cycle on the CPU "
                         "host mesh (apex_tpu.cluster), emitting "
                         "{membership_epochs, detect_ms, replan_ms, "
                         "stream_restore_ms, gathered_restore_ms, "
                         "shard_bytes_peak_host} plus a real-OS-process "
                         "FileKV heartbeat detection record")
    ap.add_argument("--observe-microbench", action="store_true",
                    help="telemetry_overhead_us stage: the fused step "
                         "with the on-device telemetry carry vs telemetry "
                         "off, at drain_every in {1,16}, CPU-forced — the "
                         "observe claim is <2%% overhead at "
                         "drain_every>=16")
    ap.add_argument("--overlap-microbench", action="store_true",
                    help="window_step_us stage: the executor overlap "
                         "knobs (ZeRO all-gather prefetch, async H2D "
                         "double-buffering) off vs on at K in {1,4,16}, "
                         "CPU-forced — emits {gather_overlap_factor, "
                         "h2d_overlap_factor, window_step_us}; both "
                         "arms are the same math DAG, so the factors "
                         "are ~1.0 on cpu and become the overlap win "
                         "on the async backends")
    ap.add_argument("--serve", action="store_true",
                    help="serve_throughput stage: the continuous-batching "
                         "paged-KV engine under a 200-session Poisson "
                         "open-loop trace, CPU-forced — emits "
                         "{tokens_per_s_per_chip, p50_ms, p99_ms, "
                         "ttft_p50_ms, pool_occupancy, decode_compiles}; "
                         "decode_compiles must stay within bucket_bound "
                         "(recompile-free decode after warmup)")
    ap.add_argument("--serve-elastic", action="store_true",
                    help="serve_elastic_recovery stage: the "
                         "membership-backed ServeFleet through one full "
                         "detect→shed→migrate→resume cycle under chaos "
                         "host loss, CPU-forced — emits {detect_ms, "
                         "migrate_ms, sessions_migrated, "
                         "sessions_shed_requeued, sessions_recomputed, "
                         "snapshot_bytes_peak_host, epoch}; every "
                         "request must complete across the shrink")
    ap.add_argument("--rollout", action="store_true",
                    help="rollout_loop stage: the generate-then-train "
                         "runtime end to end (seeded prompts → "
                         "speculative serve → bounded-staleness buffer "
                         "→ fused train step → measured weight publish "
                         "+ online draft distillation), CPU-forced — "
                         "emits {rollout_tokens_per_s, "
                         "train_steps_per_s, weight_sync_ms, "
                         "zero_copy_frac, accept_rate_trend, "
                         "buffer_staleness_p50}; zero_copy_frac is 1.0 "
                         "on cpu (layout-identical publish, donation "
                         "off)")
    ap.add_argument("--budget-s", type=float,
                    default=float(os.environ.get("GRAFT_BENCH_BUDGET_S", 540)))
    ap.add_argument("--ledger", type=str, default=None,
                    help="resumable stage ledger (JSON): stages already "
                         "recorded done are skipped, so a hung stage "
                         "re-runs alone instead of forcing the round")
    ap.add_argument("--stages", type=str, default=None,
                    help="comma-separated stage names to run in "
                         "sequence (e.g. 'serve,lint,elastic'); each "
                         "gets its own watchdog window and, with "
                         "--ledger, its own completion record")
    args = ap.parse_args()

    # the self-contained stages, addressable by name for --stages and
    # the ledger (one name per flag, dashes as in the flag spelling)
    stage_runners = {
        "opt-microbench": run_opt_microbench,
        "accum-microbench": run_accum_microbench,
        "lint": run_lint,
        "ckpt-microbench": run_ckpt_microbench,
        "elastic": run_elastic,
        "cluster": run_cluster,
        "observe-microbench": run_observe_microbench,
        "overlap-microbench": run_overlap_microbench,
        "serve": run_serve,
        "serve-elastic": run_serve_elastic,
        "rollout": run_rollout,
        "plan": run_plan_bench,
    }
    ledger = StageLedger(args.ledger) if args.ledger else None

    def run_stage(name):
        fn = stage_runners[name]
        start_watchdog(args.budget_s)
        if ledger is not None:
            return ledger.run(name, lambda: fn(args))
        return fn(args)

    if args.stages:
        names = [s.strip() for s in args.stages.split(",") if s.strip()]
        unknown = [n for n in names if n not in stage_runners]
        if unknown:
            fail(f"unknown_stages: {','.join(unknown)} (known: "
                 f"{','.join(sorted(stage_runners))})")
            return 1
        rc = 0
        for name in names:
            rc = run_stage(name) or rc
        return rc

    for name, flag in (("opt-microbench", args.opt_microbench),
                       ("accum-microbench", args.accum_microbench),
                       ("lint", args.lint),
                       ("ckpt-microbench", args.ckpt_microbench),
                       ("elastic", args.elastic),
                       ("cluster", args.cluster),
                       ("observe-microbench", args.observe_microbench),
                       ("overlap-microbench", args.overlap_microbench),
                       ("serve", args.serve),
                       ("serve-elastic", args.serve_elastic),
                       ("rollout", args.rollout),
                       ("plan", args.plan)):
        if flag:
            return run_stage(name)

    if args.pad_vocab and not args.gpt:
        fail("pad_vocab_unsupported_config: --pad-vocab applies to the "
             "--gpt config only (the GPT family implements "
             "pad_vocab_multiple)")
        return 1
    # vocab-chain implementation for the LM configs (--plain-loss is the
    # historical spelling of --loss-mode plain).  Default: chunked — the
    # round-5 in-step A/B winner on every LM config (GPT seq-128
    # 1042.9 vs 920.4 seq/s, seq-512 +15%, seq-1024 +13%, Llama +2.2%;
    # unledgered run, round 5)
    lm_mode = args.loss_mode or ("plain" if args.plain_loss else "chunked")
    if args.loss_mode and not (args.gpt or args.llama or args.seq2seq):
        fail("loss_mode_unsupported_config: --loss-mode applies to the "
             "--gpt, --llama and --seq2seq configs")
        return 1
    if args.grad_accum > 1 and not (args.gpt or args.llama):
        fail("grad_accum_unsupported_config: --grad-accum applies to "
             "the --gpt and --llama configs")
        return 1
    start_watchdog(args.budget_s)
    log(f"start (watchdog {args.budget_s:.0f}s)")

    # ONE metric name per config, used by both the failure diagnostics
    # (fail()) and the success emit paths below — computed here so a
    # rename can never desync a failed run's JSON from a successful
    # run's.  Branch order mirrors the dispatch order below.
    def config_metric():
        if args.profile:
            kind = ("bert" if args.bert else "gpt" if args.gpt
                    else "llama" if args.llama else "vit" if args.vit
                    else "resnet")
            return f"{kind}_step_op_time_attribution", "us_matched"
        if args.kernels_timing:
            return "pallas_kernel_speedup_vs_xla", "x_geomean"
        if args.kernels:
            return "pallas_kernel_parity", "pass"
        if args.spec_decode:
            d = "" if args.draft == "trained" else f"_{args.draft}draft"
            return (f"llama_125m_speculative_decode{d}_tokens_per_sec"
                    f"_per_chip", "tokens/sec/chip")
        if args.gpt_decode:
            q = "_int8" if args.int8 else ""
            q += "_kvint8" if args.kv_int8 else ""
            return (f"gpt2_small_greedy_decode{q}_tokens_per_sec_per_chip",
                    "tokens/sec/chip")
        if args.llama_decode:
            q = "_int8" if args.int8 else ""
            q += "_kvint8" if args.kv_int8 else ""
            w = f"_window{args.window}" if args.window else ""
            return (f"llama_125m_greedy_decode{q}{w}_tokens_per_sec_"
                    f"per_chip", "tokens/sec/chip")
        ad = (f"attndrop{args.attn_dropout:g}_"
              if args.attn_dropout else "")
        if args.bert:
            fh = "fullhead_" if args.full_mlm_head else ""
            return (f"bert_base_mlm_seq{args.seq_len}_{ad}{fh}"
                    "sequences_per_sec_per_chip_ampO2",
                    "sequences/sec/chip")
        # non-default vocab-chain arms tag the metric so headline
        # history rows stay comparable (untagged = the shipping default,
        # now chunked; round-4 untagged rows were the fused mode the
        # chunked A/B superseded)
        lt = f"{lm_mode}loss_" if lm_mode != "chunked" else ""
        ga = f"ga{args.grad_accum}_" if args.grad_accum > 1 else ""
        ga += "dynscale_" if args.dynamic_scale else ""
        if args.gpt:
            pv = "padvocab_" if args.pad_vocab else ""
            return (f"gpt2_{args.gpt_size}_causal_lm_seq{args.seq_len}_"
                    f"{ad}{pv}{lt}{ga}sequences_per_sec_per_chip_ampO2",
                    "sequences/sec/chip")
        if args.llama:
            return (f"llama_125m_causal_lm_seq{args.seq_len}_{lt}{ga}"
                    "sequences_per_sec_per_chip_ampO2",
                    "sequences/sec/chip")
        if args.seq2seq:
            return (f"seq2seq_base_seq{args.seq_len}_"
                    "sequences_per_sec_per_chip_ampO2",
                    "sequences/sec/chip")
        if args.vit:
            return ("vit_s16_imagenet_images_per_sec_per_chip_ampO2",
                    "images/sec/chip")
        if args.dcgan:
            return ("dcgan64_multi_loss_images_per_sec_per_chip_ampO1",
                    "images/sec/chip")
        if args.nhwc:
            return ("resnet50_imagenet_nhwc_images_per_sec_per_chip_"
                    "ampO2", "images/sec/chip")
        return "resnet50_imagenet_images_per_sec_per_chip_ampO2", \
            "images/sec/chip"

    metric_name, metric_unit = config_metric()
    FAIL_METRIC.update(metric=metric_name, unit=metric_unit)

    # validate cheap config errors BEFORE backend init (and emit the
    # promised diagnostic JSON line)
    if (args.int8 or args.kv_int8) and not (args.gpt_decode
                                            or args.llama_decode):
        fail("int8_unsupported_config: --int8/--kv-int8 are quantized "
             "DECODE measurements; pair them with --gpt-decode or "
             "--llama-decode")
        return 1
    if args.window is not None and not args.llama_decode:
        fail("window_unsupported_config: --window is the rolling-cache "
             "arm of --llama-decode")
        return 1
    if args.gpt_decode and args.llama_decode:
        fail("decode_config_conflict: pick ONE of --gpt-decode / "
             "--llama-decode (the metric names one model)")
        return 1
    if args.nhwc and (args.bert or args.gpt or args.llama or args.seq2seq
                      or args.vit or args.dcgan or args.gpt_decode
                      or args.llama_decode or args.spec_decode):
        fail("nhwc_unsupported_config: --nhwc is the channels-last arm "
             "of the resnet config (default / --sweep / --profile)")
        return 1
    if args.profile and (args.seq2seq or args.gpt_decode
                         or args.llama_decode or args.dcgan):
        fail("profile_unsupported_config: --profile supports the "
             "resnet (default), --gpt, --bert, --llama and --vit "
             "configs")
        return 1
    sweep_batches = None
    if args.sweep:
        if args.profile or args.kernels or args.kernels_timing \
                or args.gpt_decode or args.llama_decode \
                or args.spec_decode:
            fail("sweep_unsupported_config: --sweep applies to the "
                 "throughput configs (resnet/--gpt/--bert/--seq2seq)")
            return 1
        try:
            sweep_batches = [int(b) for b in args.sweep.split(",")]
            if not sweep_batches or min(sweep_batches) < 1:
                raise ValueError(args.sweep)
        except ValueError:
            fail(f"sweep_parse_failed: --sweep must be a comma-separated "
                 f"list of positive ints, got {args.sweep!r}")
            return 1

    try:
        stage("backend_init")
        devices = init_backend()
    except Exception as e:
        fail(f"backend_init_failed: {type(e).__name__}: {e}")
        return 1

    if args.profile:
        # unsupported combos already rejected before backend init
        kind = ("bert" if args.bert else "gpt" if args.gpt
                else "llama" if args.llama else "vit" if args.vit
                else "resnet")
        batch = args.batch or (64 if kind in ("bert", "gpt", "llama")
                               else 128)
        try:
            res = run_profile(kind, batch, args.seq_len,
                              plain_loss=args.plain_loss,
                              nhwc=args.nhwc,
                              remat=args.remat, size=args.gpt_size,
                              loss_mode=args.loss_mode)
        except Exception as e:
            fail(f"profile_failed: {type(e).__name__}: {e}")
            return 1
        emit({"metric": metric_name,
              "value": res["matched_us"], "unit": metric_unit,
              "vs_baseline": None, **res})
        return 0

    if args.kernels_timing:
        stage("kernel_timing")
        try:
            with _pin_flash_dispatch():
                res, gmean = run_kernel_timing()
        except Exception as e:
            fail(f"kernel_timing_failed: {type(e).__name__}: {e}")
            return 1
        emit({"metric": metric_name,
              "value": round(gmean, 3) if gmean else None,
              "unit": metric_unit, "vs_baseline": None, "kernels": res})
        return 0

    if args.kernels:
        stage("kernel_checks")
        res = run_kernel_checks()
        ok = (res.get("layer_norm") == "pass"
              and res.get("rms_norm") == "pass"
              and res.get("attention") == "pass"
              and res.get("xentropy") == "pass"
              and res.get("vmem_guard") == "pass")
        emit({"metric": metric_name, "value": 1.0 if ok else 0.0,
              "unit": metric_unit, "vs_baseline": None, "kernels": res})
        return 0

    if args.spec_decode:
        batch = args.batch or 1
        spec_new_tokens, spec_k = 128, 4
        try:
            spec_toks, plain_toks, compile_s, spec_stats = \
                run_spec_decode_throughput(
                    batch, args.seq_len, new_tokens=spec_new_tokens,
                    k=spec_k, draft_mode=args.draft,
                    draft_train_steps=args.draft_steps)
        except Exception as e:
            fail(f"spec_decode_failed: {type(e).__name__}: {e}")
            return 1
        emit({"metric": metric_name,
              "value": round(spec_toks, 1), "unit": metric_unit,
              "vs_baseline": round(spec_toks / plain_toks, 3),
              "batch": batch, "prompt_len": args.seq_len,
              "new_tokens": spec_new_tokens, "k": spec_k,
              "rounds": spec_stats["rounds"],
              "tokens_per_round": round(spec_stats["tokens_per_round"], 2),
              "draft_acceptance": round(spec_stats["draft_acceptance"], 3),
              "draft_mode": args.draft,
              "draft_train_steps": (args.draft_steps
                                    if args.draft == "trained" else None),
              "plain_tokens_per_sec": round(plain_toks, 1),
              "compile_s": round(compile_s, 1),
              "device_kind": (devices[0].device_kind or "").lower(),
              "kernels": None})
        return 0

    if args.gpt_decode or args.llama_decode:
        batch = args.batch or 8
        try:
            if args.llama_decode:
                toks, dt, compile_s = run_llama_decode_throughput(
                    batch, args.seq_len, int8=args.int8,
                    kv_int8=args.kv_int8, window=args.window)
            else:
                toks, dt, compile_s = run_decode_throughput(
                    batch, args.seq_len, int8=args.int8,
                    kv_int8=args.kv_int8)
        except Exception as e:
            fail(f"decode_failed: {type(e).__name__}: {e}")
            return 1
        emit({"metric": metric_name,
              "value": round(toks, 1), "unit": metric_unit,
              "vs_baseline": None, "batch": batch,
              "prompt_len": args.seq_len, "new_tokens": 128,
              "window": args.window,
              "call_time_s": round(dt, 3),
              "compile_s": round(compile_s, 1),
              "device_kind": (devices[0].device_kind or "").lower(),
              "kernels": None})
        return 0

    def run_one(batch):
        """One throughput measurement at ``batch`` for the selected
        config.  Returns (dt, compile_s, flops, flops_source)."""
        if args.bert:
            return run_bert_throughput(batch, args.seq_len, args.iters,
                                       args.warmup,
                                       plain_loss=args.plain_loss,
                                       attn_dropout=args.attn_dropout,
                                       gathered_mlm=not args.full_mlm_head)
        if args.seq2seq:
            return run_seq2seq_throughput(batch, args.seq_len, args.iters,
                                          args.warmup,
                                          plain_loss=args.plain_loss,
                                          loss_mode=lm_mode)
        if args.gpt:
            return run_gpt_throughput(batch, args.seq_len, args.iters,
                                      args.warmup, remat=args.remat,
                                      size=args.gpt_size,
                                      loss_mode=lm_mode,
                                      attn_dropout=args.attn_dropout,
                                      pad_vocab=args.pad_vocab,
                                      grad_accum=args.grad_accum,
                                      chunk_rows=args.chunk_rows,
                                      dynamic_scale=args.dynamic_scale)
        if args.llama:
            return run_llama_throughput(batch, args.seq_len, args.iters,
                                        args.warmup, remat=args.remat,
                                        loss_mode=lm_mode,
                                        grad_accum=args.grad_accum,
                                        chunk_rows=args.chunk_rows)
        if args.vit:
            return run_vit_throughput(batch, args.iters, args.warmup)
        if args.dcgan:
            return run_dcgan_throughput(batch, args.iters, args.warmup)
        return run_throughput(batch, args.iters, args.warmup,
                              nhwc=args.nhwc,
                              flat_optim=args.flat_optim)

    if args.sweep:
        # batch sweep in ONE process (warm backend shared): one JSON line
        # per batch, no kernel checks, no fallback — a failed batch
        # reports and the sweep continues; exit 1 if NO point succeeds
        cfg = ("bert" if args.bert else
               f"gpt2_{args.gpt_size}" if args.gpt else
               "llama_125m" if args.llama else
               "seq2seq" if args.seq2seq else
               "vit_s16" if args.vit else
               "dcgan64" if args.dcgan else
               "resnet50_nhwc" if args.nhwc else "resnet50")
        peak, kind = peak_tflops(devices[0])
        ok = 0
        for batch in sweep_batches:
            base = {"metric": f"{cfg}_batch_sweep_point",
                    "unit": "items/sec/chip", "vs_baseline": None,
                    "config": cfg, "seq_len": args.seq_len,
                    "plain_loss": bool(args.plain_loss), "batch": batch}
            try:
                dt, compile_s, flops, flops_source = run_one(batch)
            except Exception as e:
                emit({**base, "value": None,
                      "error": f"{type(e).__name__}: {e}"})
                continue
            ok += 1
            tfl = flops / dt / 1e12
            emit({**base, "value": round(batch / dt, 1),
                  "step_time_ms": round(dt * 1e3, 2),
                  "compile_s": round(compile_s, 1),
                  "tflops": round(tfl, 2),
                  "mfu": round(tfl / peak, 4),
                  "device_kind": kind, "flops_source": flops_source,
                      "kernels": None})
        return 0 if ok else 1

    dt = compile_s = flops = None
    flops_source = "none"
    err = None
    # per-config default batch; an explicitly requested batch is honored
    first_batch = args.batch
    if first_batch is None:
        # vit: 32 is the measured v5e throughput peak (unledgered run,
        # round 5: 2735 img/s vs 1843 at the old 128 — the materializing
        # S=197 attention's scores working set grows with batch and
        # falls off a cliff past ~64)
        first_batch = 64 if (args.bert or args.gpt or args.llama
                             or args.seq2seq) \
            else 32 if args.vit else 128
        log(f"default batch: {first_batch}")
    for batch in [first_batch, first_batch // 2, first_batch // 4]:
        if batch < 1:
            break
        try:
            dt, compile_s, flops, flops_source = run_one(batch)
            break
        except Exception as e:
            err = e
            log(f"batch {batch} failed: {type(e).__name__}: {e}")
            continue
    else:
        batch = None
    if dt is None:
        fail(f"throughput_failed: {type(err).__name__}: {err}")
        return 1

    imgs_per_sec = batch / dt
    tflops = flops / dt / 1e12
    peak, kind = peak_tflops(devices[0])
    mfu = tflops / peak

    kernels = None
    if not args.no_kernels:
        stage("kernel_checks")
        try:
            kernels = run_kernel_checks()
        except Exception as e:
            kernels = {"error": f"{type(e).__name__}: {e}"}

    stage("report")
    is_resnet = not (args.bert or args.gpt or args.llama or args.seq2seq
                     or args.vit or args.dcgan)
    if is_resnet:
        # measured-anchor convention: the commonly reported V100 Apex-O2
        # ResNet-50 number (BASELINE.md)
        vs_baseline = round(imgs_per_sec / V100_APEX_O2_IMGS_PER_SEC, 3)
        anchor_note = "v100_apex_o2_measured_800_img_s"
    else:
        # derived-anchor convention (see V100_EST_SUSTAINED_TFLOPS):
        # a V100 at 30% MFU of its 125 TFLOP/s fp16 peak on this exact
        # step's FLOPs; ratio reduces to achieved TFLOP/s / 37.5
        vs_baseline = round(tflops / V100_EST_SUSTAINED_TFLOPS, 3)
        anchor_note = ("v100_est_30pct_mfu_125tflops: anchor_items_s="
                       f"{V100_EST_SUSTAINED_TFLOPS * 1e12 * batch / flops:.1f}")
    emit({
        "metric": metric_name,
        "value": round(imgs_per_sec, 1),
        "unit": metric_unit,
        "vs_baseline": vs_baseline,
        "baseline_anchor": anchor_note,
        "batch": batch,
        "step_time_ms": round(dt * 1e3, 2),
        "compile_s": round(compile_s, 1),
        "tflops": round(tflops, 2),
        "mfu": round(mfu, 4),
        "device_kind": kind,
        "flops_source": flops_source,
        "kernels": kernels,
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
