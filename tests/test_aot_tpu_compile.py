"""The main path's kernels and whole programs, compiled for a described
TPU v5e at GPT-2-small widths.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described and not attached (``on-chip-measurement`` guide,
section 2).  Nothing runs, so these say nothing about results or times:
they catch what interpret mode cannot — a tile the chip refuses, a kernel
over its fast-memory budget, a program over the chip's 16 GB — before a
PR spends chip time on it.  Skipped where the topology cannot be
described.

Code that asks ``jax.default_backend()`` still sees the CPU here, so each
case steers it *in the test*: ``force_mode("compiled")`` for the kernel
mode, ``donate_state=True`` / ``donate_argnums`` for donation.
"""
import json
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from apex_tpu.kernels.dispatch import force_mode

pytestmark = pytest.mark.kernels

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
VOCAB, HIDDEN, LAYERS, HEADS, HEAD_DIM = 50257, 768, 12, 12, 64


@pytest.fixture(scope="module")
def chip():
    """One described v5e device, with the persistent compile cache off
    around the module: a compile for an unattached chip is written to
    the cache but cannot be read back, and the next one would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"TPU topology cannot be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _sds(shape, dtype, chip):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _on(chip, tree):
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, chip), tree)


def _check(compiled, min_custom_calls):
    """Kernel present where one is expected, and the program fits."""
    assert compiled.as_text().count("tpu_custom_call") >= min_custom_calls
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.1f} GiB does not fit 16 GB"
    return ma


def _flash(window=None):
    from apex_tpu.contrib.multihead_attn.attn_funcs import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, sliding_window=window)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))
    return fwd, jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("which,batch,seq,window,calls", [
    ("fwd", 8, 1024, None, 1),
    ("bwd", 8, 1024, None, 2),          # forward + the one backward
    ("bwd", 4, 2048, 256, 2),
], ids=["flash_fwd", "flash_bwd", "flash_windowed"])
def test_flash_attention_compiles(chip, which, batch, seq, window, calls):
    fwd, bwd = _flash(window)
    q = _sds((batch, HEADS, seq, HEAD_DIM), jnp.bfloat16, chip)
    with force_mode("compiled"):
        compiled = jax.jit(fwd if which == "fwd" else bwd).lower(
            q, q, q).compile()
    _check(compiled, calls)


RESIDENT = ["flash_attn_bwd", "flash_attn_fwd"]
TILED = ["flash_attn_bwd_dkv", "flash_attn_bwd_dq", "flash_attn_fwd"]


@pytest.mark.parametrize("bh,seq,dim,causal,kernels", [
    (16 * HEADS, 1024, HEAD_DIM, True, RESIDENT),   # the train cell's call
    (96, 2048, 64, True, RESIDENT),     # the longest the rule admits,
    (32, 2048, 128, True, RESIDENT),    # at both head widths,
    (32, 2048, 128, False, RESIDENT),   # and with every extent whole
    (16, 2304, 64, True, TILED),        # a ninth row block
    (16, 2048, 256, True, TILED),       # the first shapes past the
    (16, 4096, 128, True, TILED),       # estimate
], ids=["cell", "s2048_d64", "s2048_d128", "s2048_d128_whole",
        "s2304_d64", "s2048_d256", "s4096_d128"])
def test_flash_resident_boundary_compiles(chip, bh, seq, dim, causal,
                                          kernels):
    """Both sides of the resident path's VMEM boundary, held by the
    v5e compiler and not by ``_resident_vmem_estimate``'s arithmetic
    alone: what the rule admits compiles as the resident pair, and the
    first shapes past it take the tiled three."""
    from apex_tpu.contrib.multihead_attn.attn_funcs import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal)
                       .astype(jnp.float32))

    q = _sds((1, bh, seq, dim), jnp.bfloat16, chip)
    with force_mode("compiled"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).compile()
    _check(compiled, len(kernels))
    assert sorted(_kernel_names(compiled)) == kernels


@pytest.mark.parametrize("t,b,heads,dim,causal,packed,kernels", [
    (1024, 16, HEADS, HEAD_DIM, True, True, RESIDENT),  # the train cell's
    (1536, 2, 12, 64, True, True, RESIDENT),    # the longest the rule admits:
    (1536, 2, 12, 64, False, True, RESIDENT),   # pairs of heads (two heads'
    (2048, 2, 8, 128, True, True, RESIDENT),    # scores at once), a head a
    (2048, 2, 8, 128, False, True, RESIDENT),   # lane row, every extent whole
    (600, 2, 4, 64, True, True, RESIDENT),      # rows padded to a row block
    (1792, 2, 12, 64, True, False, RESIDENT),   # the first pairs past it
    (2048, 2, 3, 64, True, False, RESIDENT),    # take the split entry, as
                                                # an odd head does
    (2304, 2, 2, 64, True, False, TILED),       # a ninth row block
    (4096, 1, 2, 128, True, False, TILED),      # past every estimate
], ids=["cell", "s1536_d64_pairs", "s1536_d64_pairs_whole", "s2048_d128",
        "s2048_d128_whole", "s600_d64_padded", "s1792_d64_pairs",
        "odd_heads", "s2304_d64", "s4096_d128"])
def test_flash_packed_boundary_compiles(chip, t, b, heads, dim, causal,
                                        packed, kernels):
    """Both sides of the packed entry's rule, held by the v5e compiler:
    what it admits compiles as the resident pair on (T, 384) blocks of
    the projection's (B, T, 3.H.D) output, within the kernel's VMEM (the
    estimate counts a 384-lane block for three of 128 lanes), and what
    it does not takes the split entry's kernels on (B.H, T, D)."""
    from apex_tpu.contrib.multihead_attn.attn_funcs import self_attn_func
    e = heads * dim

    def loss(x, iw, ow):
        return jnp.sum(self_attn_func(
            False, True, heads, dim ** -0.5, x, iw, ow, use_flash=True,
            causal=causal).astype(jnp.float32))

    with force_mode("compiled"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            _sds((t, b, e), jnp.bfloat16, chip),
            _sds((3 * e, e), jnp.bfloat16, chip),
            _sds((e, e), jnp.bfloat16, chip)).compile()
    _check(compiled, len(kernels))
    assert sorted(_kernel_names(compiled)) == kernels
    # the forward's first operand, its rows padded to whole blocks
    first = _kernel_operands(compiled, "flash_attn_fwd")[0][0]
    lead, rows, width = map(int, re.findall(r"\d+", first)[1:])
    assert rows >= t and (lead, width) == (
        (b, 3 * e) if packed else (b * heads, dim)), first


def test_flash_attention_with_bias_and_dropout_compiles(chip):
    """The variant that needs most fast memory a grid step — a full
    (Sq, Sk) bias block and the dropout hash beside the scores — at the
    tiles bf16 operands take: what keeps them at 512 x 1024."""
    from apex_tpu.contrib.multihead_attn.attn_funcs import flash_attention

    def loss(q, k, v, bias, seed):
        return jnp.sum(flash_attention(
            q, k, v, bias=bias, causal=True, dropout_p=0.1,
            dropout_seed=seed).astype(jnp.float32))

    q = _sds((4, HEADS, 2048, HEAD_DIM), jnp.bfloat16, chip)
    bias = _sds((4, 2048, 2048), jnp.float32, chip)
    seed = _sds((), jnp.int32, chip)
    with force_mode("compiled"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q, bias, seed).compile()
    _check(compiled, 3)


def test_fused_adam_compiles(chip):
    from apex_tpu.kernels.multi_tensor import fused_adam

    shapes = [(VOCAB, HIDDEN), (HIDDEN, 3 * HIDDEN), (HIDDEN,), (257,)]
    lists = [[_sds(s, jnp.float32, chip) for s in shapes]] * 4
    flag = _sds((), jnp.int32, chip)
    with force_mode("compiled"):
        compiled = jax.jit(lambda f, t: fused_adam(
            f, t, 1e-3, 0.9, 0.999, 1e-8, 7, 1, True, 0.01)).lower(
                flag, lists).compile()
    _check(compiled, 1)


def _gpt2_small(**kw):
    import apex_tpu.nn as nn
    from apex_tpu.models import gpt2_small
    nn.manual_seed(0)
    return gpt2_small(vocab_size=VOCAB, max_positions=1024, **kw)


def _kernel_calls(compiled):
    """Names of the program's Pallas custom calls: they come from
    ``pallas_call(name=...)`` and are what the device trace shows."""
    return [ln.split("=")[0] for ln in compiled.as_text().splitlines()
            if "tpu_custom_call" in ln and " custom-call(" in ln]


def _kernel_names(compiled):
    """The same, bare: ``%transpose_jvp_flash_attn_bwd__.3`` (the
    backward of a custom VJP under ``grad``) is ``flash_attn_bwd``."""
    return [re.sub(r"^%(transpose_)?(jvp_)?|_*(\.\d+)?$", "",
                   c.replace("ROOT", "").strip())
            for c in _kernel_calls(compiled)]


def _kernel_operands(compiled, kernel):
    """The operand types (``bf16[96,1024,64]``) of each custom call that
    ``kernel`` names, as the compiled program constrains them."""
    found = []
    for ln, name in zip((ln for ln in compiled.as_text().splitlines()
                         if "tpu_custom_call" in ln
                         and " custom-call(" in ln),
                        _kernel_names(compiled)):
        if name == kernel:
            constraints = ln.split("operand_layout_constraints={")[1]
            found.append(re.findall(r"(\w+\[[\d,]*\])\{",
                                    constraints.split("}}")[0]))
    return found


def test_decode_program_compiles(chip):
    """The paged decode tick at the pool ``chip_smoke.py`` serves from
    (2048 blocks of 16): batch bucket 8, table bucket 64, pool donated,
    the table-reading kernel in every layer."""
    from apex_tpu.serve import kernels as serve_kernels
    from apex_tpu.serve.pool import init_pool_buffer

    num_blocks, block_size, batch, table = 2048, 16, 8, 64
    model = _gpt2_small(dropout=0.0, attn_dropout=0.0).bfloat16()
    model.eval()
    params = list(model.parameters()) + list(model.buffers())
    fn = serve_kernels.build_decode_fn(model, params, block_size,
                                       num_blocks)
    vals = _on(chip, [p.data for p in params])
    pool = _on(chip, jax.eval_shape(lambda: init_pool_buffer(
        LAYERS, HEADS, HEAD_DIM, num_blocks, block_size, jnp.bfloat16)))
    i32 = jnp.int32
    with force_mode("compiled"):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            vals, pool, _sds((batch,), i32, chip),
            _sds((batch,), i32, chip),
            _sds((batch, table), i32, chip)).compile()
    ma = _check(compiled, LAYERS)
    assert ma.alias_size_in_bytes >= pool.size * 2      # pool updated in place
    assert sum("paged_attention_decode" in c
               for c in _kernel_calls(compiled)) == LAYERS


# -- the serve programs of the benchmark's gpt2-medium cell ----------------
# (perfbench/configs/gpt2-medium.json: the engine settings of a
# deployment).  What these pin: the programs take the KV pool where it
# lies.  Its format is the program's (``init_pool_buffer``), it is
# aliased input to output, and nothing but the in-place row writes
# produces a buffer of its size: no copy, transpose, gather or convert
# of the pool, which is what made a decode step 89 ms (PERF.md, PR 27).

USABLE_BYTES = int(15.75 * 2 ** 30)     # the compiler's own limit on v5e


@pytest.fixture(scope="module")
def medium():
    """gpt2-medium and the cell's engine settings."""
    import apex_tpu.nn as nn
    from apex_tpu.models import GptModel
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "..", "perfbench", "configs",
                           "gpt2-medium.json")) as f:
        cfg = json.load(f)
    nn.manual_seed(0)
    model = GptModel(
        vocab_size=cfg["vocab_size"], hidden=cfg["n_embd"],
        layers=cfg["n_layer"], heads=cfg["n_head"],
        max_positions=cfg["n_positions"], dropout=0.0, attn_dropout=0.0,
        attn_bias=cfg["attn_bias"]).to(
            jnp.dtype(cfg["serve"]["weights_dtype"]))
    model.eval()
    return cfg, model


def _serve_program(chip, medium, which, batch):
    from apex_tpu.serve import kernels as serve_kernels
    from apex_tpu.serve.pool import init_pool_buffer
    cfg, model = medium
    sv = cfg["serve"]
    heads = cfg["n_head"]
    params = list(model.parameters()) + list(model.buffers())
    vals = _on(chip, [p.data for p in params])
    pool = _on(chip, jax.eval_shape(lambda: init_pool_buffer(
        cfg["n_layer"], heads, cfg["n_embd"] // heads, sv["num_blocks"],
        sv["block_size"], jnp.dtype(sv["cache_dtype"]))))
    nb = cfg["n_positions"] // sv["block_size"]         # a full context

    def i32(*shape):
        return _sds(shape, jnp.int32, chip)
    build = {"decode": serve_kernels.build_decode_fn,
             "prefill": serve_kernels.build_prefill_fn}[which]
    fn = build(model, params, sv["block_size"], sv["num_blocks"])
    args = (i32(batch), i32(batch), i32(batch, nb)) if which == "decode" \
        else (i32(1, sv["prefill_chunk"]), i32(1, nb), i32(), i32())
    with force_mode("compiled"):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            vals, pool, *args).compile()
    return compiled, pool


def _pool_sized_results(compiled, pool):
    """``[(opcode, line)]`` of every instruction whose result is an
    array at least as large as the pool."""
    head = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* "
                      r"([\w\-]+)\(")
    out = []
    for ln in compiled.as_text().splitlines():
        m = head.match(ln)
        if not m:
            continue
        n = 1
        for d in m.group(1).split(","):
            n *= int(d)
        if n >= pool.size:
            out.append((m.group(2), ln))
    return out


@pytest.mark.parametrize("which,batch", [
    ("decode", 16), ("decode", 32), ("prefill", 1)],
    ids=["decode_b16", "decode_b32", "prefill_chunk512"])
def test_serve_programs_take_the_pool_where_it_lies(chip, medium, which,
                                                    batch):
    compiled, pool = _serve_program(chip, medium, which, batch)
    ma = _check(compiled, 0)
    pool_bytes = pool.size * pool.dtype.itemsize
    assert ma.alias_size_in_bytes >= pool_bytes         # updated in place
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    # max_batch 32 fits (it was refused over 2048 blocks at PR 25)
    assert total < USABLE_BYTES
    # the parent's decode program kept 9.02 GiB of temporaries at B=16
    assert ma.temp_size_in_bytes < 2 ** 30, ma.temp_size_in_bytes / 2 ** 30
    text = compiled.as_text()
    for op, ln in _pool_sized_results(compiled, pool):
        # a pool-sized result is the pool itself (a parameter, a view of
        # it) or a row write: a scatter on the aliased buffer, alone or
        # as a fusion's body (out of place it would be 3 GiB of
        # temporaries, refused above)
        assert op in ("parameter", "bitcast", "scatter", "fusion"), ln[:300]
        if op == "fusion":
            body = _called_computation(text, ln)
            assert " scatter(" in body, ln[:300]


def _called_computation(text, fusion_line):
    """The body of the computation a fusion instruction calls."""
    name = re.search(r"calls=(%[\w.\-]+)", fusion_line).group(1)
    start = text.index(f"\n{name} ")
    return text[start:text.index("\n}", start)]


def test_decode_program_reads_through_the_block_table(chip, medium):
    """The table-reading kernel is in every layer of the cell's decode
    program under its own name (one trace and one lowering shared by
    the layers: the layer is an operand), and no gather of the tables'
    blocks is left beside it."""
    compiled, pool = _serve_program(chip, medium, "decode", 16)
    cfg, _ = medium
    calls = _kernel_calls(compiled)
    assert sum("paged_attention_decode" in c for c in calls) \
        == cfg["n_layer"], calls
    # gather_kv reads through a flat (layers*2*num_blocks, ...) view of
    # the pool: with the kernel in, nothing takes that view
    flat = pool.shape[0] * pool.shape[1] * pool.shape[2]
    assert f"[{flat},{pool.shape[3]},{pool.shape[4]}]" \
        not in compiled.as_text()


# -- the serve programs of the benchmark's latent-attention MoE cell -----------
# (perfbench/configs/gigachat3.1-702b-a36b-ep16.json at its published
# widths: 8.58 GB of bf16 weights as abstract parameters, a latent pool
# of 32769 blocks of 16 rows of 640).  The same pin as above for a pool
# of one stream, and both new kernels under their own names.


@pytest.fixture(scope="module")
def latent():
    """The cell's model with parameters that have shapes and no values,
    and its engine settings."""
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.join(here, "..", "perfbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from pb import cells
    with open(os.path.join(bench, "configs",
                           "gigachat3.1-702b-a36b-ep16.json")) as f:
        cfg = json.load(f)
    # the benchmark's own way to the program's model (abstract parameters)
    model = cells.family_module(cfg["builder"]).model(cfg)
    model.eval()
    return cfg, model


@pytest.mark.parametrize("which,batch", [("decode", 128), ("prefill", 1)],
                         ids=["decode_b128", "prefill_chunk512"])
def test_latent_serve_programs_take_the_pool_where_it_lies(chip, latent,
                                                           which, batch):
    from apex_tpu.serve import kernels as serve_kernels
    from apex_tpu.serve.pool import init_pool_buffer
    cfg, model = latent
    sv = cfg["serve"]
    params = list(model.parameters())
    # the bf16 weights a deployment serves (a norm's gain is built
    # float32 and published bf16 with the rest)
    vals = [_sds(p.shape, jnp.bfloat16, chip) for p in params]
    streams, heads, head_dim = model.blocks[0].cache_rows
    assert (streams, heads, head_dim) == (1, 1, 640)
    pool = _on(chip, jax.eval_shape(lambda: init_pool_buffer(
        len(model.blocks), heads, head_dim, sv["num_blocks"],
        sv["block_size"], jnp.dtype(sv["cache_dtype"]), streams=streams)))
    nb = cfg["max_position_embeddings"] // sv["block_size"]

    def i32(*shape):
        return _sds(shape, jnp.int32, chip)
    build = {"decode": serve_kernels.build_decode_fn,
             "prefill": serve_kernels.build_prefill_fn}[which]
    fn = build(model, params, sv["block_size"], sv["num_blocks"])
    args = (i32(batch), i32(batch), i32(batch, nb)) if which == "decode" \
        else (i32(1, sv["prefill_chunk"]), i32(1, nb), i32(), i32())
    with force_mode("compiled"):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            vals, pool, *args).compile()
    ma = _check(compiled, 0)
    pool_bytes = pool.size * pool.dtype.itemsize
    assert ma.alias_size_in_bytes >= pool_bytes         # updated in place
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < USABLE_BYTES, total / 2 ** 30
    assert ma.temp_size_in_bytes < 2 ** 30, ma.temp_size_in_bytes / 2 ** 30
    text = compiled.as_text()
    for op, ln in _pool_sized_results(compiled, pool):
        assert op in ("parameter", "bitcast", "scatter", "fusion"), ln[:300]
        if op == "fusion":
            assert " scatter(" in _called_computation(text, ln), ln[:300]
    calls = _kernel_calls(compiled)
    layers = len(model.blocks)
    routed = layers - cfg["first_k_dense_replace"]
    # two grouped matmuls a routed layer (gate | up, then down)
    assert sum("routed_experts" in c for c in calls) == 2 * routed, calls
    assert sum("latent_attention_decode" in c for c in calls) \
        == (layers if which == "decode" else 0), calls
    if which == "decode":
        # the table reader is in: nothing takes gather_kv's flat view
        flat = pool.shape[0] * pool.shape[1] * pool.shape[2]
        assert f"[{flat},{pool.shape[3]},{pool.shape[4]}]" not in text


TRAIN_BATCH = 16


@pytest.fixture(scope="module")
def train_step(chip):
    """The whole GPT-2-small fused step at the train cell's 16 x 1024 —
    bf16, FusedAdam, chunked LM-head loss, no dropout and no attention
    biases, state donated — with the flash kernels in, compiled once for
    the cases below."""
    from apex_tpu.contrib.xentropy import make_chunked_lm_loss
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    model = _gpt2_small(dropout=0.0, attn_dropout=0.0, attn_bias=False,
                        output_hidden=True)
    opt = FusedAdam(list(model.parameters()), lr=6e-4, weight_decay=0.1)
    step = make_train_step(
        model, opt, make_chunked_lm_loss(vocab_size=VOCAB, padding_idx=-1),
        half_dtype=jnp.bfloat16, loss_scale=1.0, donate_state=True)
    ids = _sds((TRAIN_BATCH, 1024), jnp.int32, chip)
    # the chip's own choice for every kernel, as ``pallas_mode`` makes it
    # there: ``force_mode("compiled")`` would also turn the norm kernels
    # on, which the chip leaves to XLA, and their custom calls pin the
    # residual stream's layout (a copy of ``bf16[1024,16,768]`` at each)
    with pytest.MonkeyPatch.context() as on_the_chip:
        on_the_chip.setattr(jax, "default_backend", lambda: "tpu")
        return jax.jit(step._raw_step_fn, donate_argnums=(0,)).lower(
            _on(chip, step.state), ids, ids).compile()


def test_fused_train_step_compiles(train_step):
    """Every attention of the step takes the packed resident pair."""
    # 12 layers x (forward + the one backward kernel)
    _check(train_step, 2 * LAYERS)
    # the kernels go by their own names in the device trace: the custom
    # calls' instruction names come from ``pallas_call(name=...)``
    calls = _kernel_names(train_step)
    assert sorted({c for c in calls if "flash" in c}) == RESIDENT, calls
    # the kernels read the QKV projection's output and write the context
    # (and read dO, and write the projection's gradient) as bf16 in the
    # batch-major layout of the projections themselves: no upcast comes
    # back in front of the MXU, and no (B.H, S, D) tensor exists
    lin = f"bf16[{TRAIN_BATCH},1024,{3 * HIDDEN}]"
    ctx = f"bf16[{TRAIN_BATCH},1024,{HIDDEN}]"
    lse = f"f32[{TRAIN_BATCH},{HIDDEN // 128},{128 // HEAD_DIM},1024]"
    for kernel, tensors in (("flash_attn_fwd", [lin]),
                            ("flash_attn_bwd", [lin, ctx, ctx, lse])):
        assert calls.count(kernel) == LAYERS, (kernel, calls)
        operands = _kernel_operands(train_step, kernel)
        assert len(operands) == LAYERS, (kernel, operands)
        for ops in operands:
            assert ops == tensors, (kernel, ops)
    assert f"[{TRAIN_BATCH * HEADS},1024,{HEAD_DIM}]" not in \
        train_step.as_text()


def _layout_instructions(compiled):
    """``(name, dtype, dims)`` of the entry computation's instructions
    that only move data: ``copy``, ``reshape``, ``transpose`` and the
    fusions XLA names after them or after ``pad``."""
    text = compiled.as_text()
    found = []
    for ln in text[text.index("ENTRY "):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", ln)
        if not m:
            continue
        name, dtype, dims, op = m.groups()
        if op in ("copy", "reshape", "transpose") or (
                op == "fusion"
                and re.match(r"(copy|reshape|transpose|pad)", name)):
            found.append((name, dtype, [int(d) for d in dims.split(",")
                                        if d]))
    return found


def test_fused_train_step_moves_no_attention_layout(train_step):
    """What ISSUE 34 rests on.  At the parent the step re-tiled the QKV
    projection's output, cut q, k, v out of it and turned them to (B.H,
    T, D), turned the context and dO back, and padded, added and twice
    turned the projection's gradient: 9 instructions and 428 MB written
    a layer, 108 instructions of ``bf16[1024,192,*]`` or
    ``bf16[16384,2304]`` in the entry computation.  The packed kernels
    read and write where the projections do, batch-major, and XLA
    carries that layout through the block: no instruction of the entry
    computation that only moves data writes a tensor as large as a
    layer's context any more (the residual stream's twelve
    ``f32[1024,16,768]`` copies went with them); what is left is the
    embedding table's copy and the row order of ``in_proj_weight`` and
    of its gradient."""
    moves = _layout_instructions(train_step)
    tokens = TRAIN_BATCH * 1024
    big = [(name, dtype, dims) for name, dtype, dims in moves
           if math.prod(dims) >= tokens * HIDDEN
           and dims != [VOCAB, HIDDEN]]
    assert not big, big
    for name, dtype, dims in moves:
        assert (dims[:2] != [1024, TRAIN_BATCH * HEADS]
                and dims != [tokens, 3 * HIDDEN]), (name, dtype, dims)
    written = sum(math.prod(dims) * (2 if dtype == "bf16" else 4)
                  for _, dtype, dims in moves)
    assert written < 0.6e9, f"{written / 1e9:.2f} GB (5.89 at the parent)"


def _computations(compiled):
    """``{name: [instruction lines]}`` of the compiled module's text."""
    comps, cur = {}, None
    for ln in compiled.as_text().splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", ln)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(ln)
    return comps


def _reached_from(comps, name, seen=None):
    """``name`` and every computation it calls, fusions included."""
    seen = set() if seen is None else seen
    if name in comps and name not in seen:
        seen.add(name)
        for ln in comps[name]:
            for callee in re.findall(
                    r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", ln):
                _reached_from(comps, callee, seen)
    return seen


def _products(comps, names):
    """Each ``convolution`` of the named computations as the list of its
    result's and its operands' dimensions."""
    found = []
    for name in names:
        dims = {}
        for ln in comps[name]:
            m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \(?\w+\[([\d,]*)\]", ln)
            if m:
                dims[m.group(1)] = [int(d) for d in m.group(2).split(",")
                                    if d]
        for ln in comps[name]:
            m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = .*? convolution\((.*?)\),",
                         ln)
            if m:
                found.append([dims[m.group(1)]] + [
                    dims[o] for o in re.findall(r"%[\w.\-]+", m.group(2))])
    return found


def test_fused_train_step_holds_one_loss_loop(train_step):
    """What ISSUE 36 rests on.  At the parent the step held two loops
    over the loss's row chunks, the second computing every chunk's logits
    again: four products as wide as the vocabulary a chunk (my CPU-side
    compile, PR 36: bodies of 1 and 3 ``convolution``s, ``d table``
    carried as ``bf16[50257,768]``, 4,003,557,888 B of temporaries).  The
    factory's loss takes its gradient with its forward: one loop, three
    such products, ``d table`` a float32 carry, and nothing as wide as
    the vocabulary times the rows leaves the loop."""
    comps = _computations(train_step)
    whiles = [ln for lines in comps.values() for ln in lines
              if re.search(r" while\(", ln)]
    assert len(whiles) == 1, whiles
    carried = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = \((.*?)\) while\(",
                       whiles[0]).group(1)
    assert f"f32[{VOCAB},{HIDDEN}]" in carried, carried
    assert not re.search(rf"\[\d+,{VOCAB}\]|\[{VOCAB},\d+,", carried), carried
    body = re.search(r"body=%?([\w.\-]+)", whiles[0]).group(1)
    wide = [p for p in _products(comps, _reached_from(comps, body))
            if any(VOCAB in dims for dims in p)]
    # logits, d hidden, d table
    assert len(wide) == 3, wide
    outside = [p for p in _products(comps, set(comps)
                                    - _reached_from(comps, body))
               if any(VOCAB in dims for dims in p)]
    assert not outside, outside
    temp = train_step.memory_analysis().temp_size_in_bytes
    assert temp < 4.6e9, (
        f"{temp} B of temporaries; 4,365,810,176 with the float32 "
        f"d table and d hidden kept from the forward to the backward "
        f"(PR 36), 4,003,557,888 at its parent")


# -- the serve programs of the benchmark's window-and-full MoE cell ------------
# (perfbench/configs/mellum2-12b-a2.5b-l8.json at its published widths:
# 7.59 GB of bf16 weights as abstract parameters, a cache of two groups:
# the 2 full layers' pool of 49153 blocks and the 6 window layers' pool,
# sized by the engine, of 16 rows of 512 each).  PR 27's pin for every
# group, and the grouped-query reader under its own name.


@pytest.fixture(scope="module")
def mixed():
    """The cell's model with parameters that have shapes and no values,
    and its engine settings."""
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.join(here, "..", "perfbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from pb import cells
    with open(os.path.join(bench, "configs",
                           "mellum2-12b-a2.5b-l8.json")) as f:
        cfg = json.load(f)
    model = cells.family_module(cfg["builder"]).model(cfg)
    model.eval()
    return cfg, model


@pytest.mark.parametrize("which,batch", [("decode", 128), ("prefill", 1)],
                         ids=["decode_b128", "prefill_chunk512"])
def test_mixed_serve_programs_take_every_pool_where_it_lies(chip, mixed,
                                                            which, batch):
    from apex_tpu.serve import kernels as serve_kernels
    from apex_tpu.serve.pool import blocks_for, init_pool_buffer
    from apex_tpu.serve.scheduler import bucket
    cfg, model = mixed
    sv = cfg["serve"]
    bs, chunk = sv["block_size"], sv["prefill_chunk"]
    params = list(model.parameters())
    vals = [_sds(p.shape, jnp.bfloat16, chip) for p in params]
    groups, _ = serve_kernels.cache_groups(model)
    assert [(g.rows, g.window, len(g.layers)) for g in groups] == \
        [((2, 4, 128), None, 2), ((2, 4, 128), 1024, 6)]
    # the sizes ServeEngine gives the two pools (serve/engine.py)
    sizes = [sv["num_blocks"],
             sv["max_batch"] * (blocks_for(1024, bs) + 2)
             + blocks_for(chunk, bs) + 1]
    assert sizes[1] == 8481
    pools = tuple(_on(chip, jax.eval_shape(lambda g=g, n=n: init_pool_buffer(
        len(g.layers), g.rows[1], g.rows[2], n, bs,
        jnp.dtype(sv["cache_dtype"]), streams=g.rows[0])))
        for g, n in zip(groups, sizes))
    # the full group's tables at a whole context, the window group's ring
    nb = (cfg["max_position_embeddings"] // bs,
          bucket(blocks_for(1024 + chunk, bs) + 2))
    assert nb == (384, 128)

    def i32(*shape):
        return _sds(shape, jnp.int32, chip)
    build = {"decode": serve_kernels.build_decode_fn,
             "prefill": serve_kernels.build_prefill_fn}[which]
    fn = build(model, params, bs, sv["num_blocks"])
    rows = batch if which == "decode" else 1
    tables = tuple(i32(rows, bucket(n)) for n in nb)
    args = (i32(batch), i32(batch), tables) if which == "decode" \
        else (i32(1, chunk), tables, i32(), i32())
    with force_mode("compiled"):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            vals, pools, *args).compile()
    ma = _check(compiled, 0)
    pool_bytes = sum(p.size * p.dtype.itemsize for p in pools)
    assert ma.alias_size_in_bytes >= pool_bytes     # both updated in place
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"\n[{which}] arguments {ma.argument_size_in_bytes / 2**30:.3f} "
          f"temporaries {ma.temp_size_in_bytes / 2**30:.3f} total "
          f"{total / 2**30:.3f} GiB; pools {pool_bytes / 2**30:.3f}")
    assert total < USABLE_BYTES, total / 2 ** 30
    assert ma.temp_size_in_bytes < 2 ** 30, ma.temp_size_in_bytes / 2 ** 30
    text = compiled.as_text()
    small = min(pools, key=lambda p: p.size)
    for op, ln in _pool_sized_results(compiled, small):
        assert op in ("parameter", "bitcast", "scatter", "fusion",
                      "tuple", "get-tuple-element"), ln[:300]
        if op == "fusion":
            assert " scatter(" in _called_computation(text, ln), ln[:300]
    calls = _kernel_calls(compiled)
    layers = len(model.blocks)
    assert sum("routed_experts" in c for c in calls) == 2 * layers, calls
    assert sum("paged_attention_decode" in c for c in calls) \
        == (layers if which == "decode" else 0), calls
    if which == "decode":
        for pool in pools:      # nothing takes gather_kv's flat view
            flat = pool.shape[0] * pool.shape[1] * pool.shape[2]
            assert f"[{flat},{pool.shape[3]},{pool.shape[4]}]" not in text


# -- the serve programs of the benchmark's state-space hybrid cell -------------
# (perfbench/configs/nemotron3-nano-30b-a3b-ep4-l13.json at its published
# widths: 4.31 GB of bf16 weights as abstract parameters; a cache of one
# group of rows, the 2 attention layers' pool of 65537 blocks, and one
# state group, the 6 Mamba layers' 257 slots of a float32 state and the
# convolution's last inputs, sized by the engine).  PR 27's pin for the
# pool and for the state buffers: no program relayouts, copies or widens
# either.


@pytest.fixture(scope="module")
def hybrid():
    """The cell's model with parameters that have shapes and no values,
    and its engine settings."""
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.join(here, "..", "perfbench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from pb import cells
    with open(os.path.join(bench, "configs",
                           "nemotron3-nano-30b-a3b-ep4-l13.json")) as f:
        cfg = json.load(f)
    model = cells.family_module(cfg["builder"]).model(
        cfg, dtype=jnp.bfloat16)
    model.eval()
    return cfg, model


@pytest.mark.parametrize("which,batch", [("decode", 256), ("prefill", 1)],
                         ids=["decode_b256", "prefill_chunk512"])
def test_hybrid_serve_programs_take_pool_and_state_where_they_lie(
        chip, hybrid, which, batch):
    from apex_tpu.serve import kernels as serve_kernels
    from apex_tpu.serve.pool import init_pool_buffer, init_state_buffers
    cfg, model = hybrid
    sv = cfg["serve"]
    bs, chunk = sv["block_size"], sv["prefill_chunk"]
    params = list(model.parameters())
    vals = [_sds(p.shape, jnp.bfloat16, chip) for p in params]
    groups, _ = serve_kernels.cache_groups(model)
    assert [(g.rows, g.window, g.layers) for g in groups] == \
        [((2, 2, 128), None, (5, 12))]
    sgroups, _ = serve_kernels.state_groups(model)
    assert [(g.state, len(g.layers)) for g in sgroups] == [
        ((((128, 4096), "float32"), ((3, 6144), "bfloat16")), 6)]
    g = groups[0]
    pool = _on(chip, jax.eval_shape(lambda: init_pool_buffer(
        len(g.layers), g.rows[1], g.rows[2], sv["num_blocks"], bs,
        jnp.dtype(sv["cache_dtype"]), streams=g.rows[0])))
    # the sizes ServeEngine gives the state groups: a row a slot, and
    # the null slot
    states = tuple(_on(chip, jax.eval_shape(
        lambda sg=sg: init_state_buffers(sg.state, len(sg.layers),
                                         sv["max_batch"])))
        for sg in sgroups)
    assert [s.shape for s in states[0]] == [(6, 257, 128, 4096),
                                            (6, 257, 3, 6144)]
    nb = cfg["max_position_embeddings"] // bs       # a whole context

    def i32(*shape):
        return _sds(shape, jnp.int32, chip)
    build = {"decode": serve_kernels.build_decode_fn,
             "prefill": serve_kernels.build_prefill_fn}[which]
    fn = build(model, params, bs, sv["num_blocks"])
    args = (i32(batch), i32(batch), i32(batch, nb), i32(batch)) \
        if which == "decode" \
        else (i32(1, chunk), i32(1, nb), i32(), i32(), i32(1))
    with force_mode("compiled"):
        compiled = jax.jit(fn, donate_argnums=(1, 2)).lower(
            vals, pool, states, *args).compile()
    ma = _check(compiled, 0)
    state_bytes = sum(s.size * s.dtype.itemsize for s in states[0])
    pool_bytes = pool.size * pool.dtype.itemsize
    # the pool and both state buffers are updated in place
    assert ma.alias_size_in_bytes >= pool_bytes + state_bytes
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    print(f"\n[{which}] arguments {ma.argument_size_in_bytes / 2**30:.3f} "
          f"temporaries {ma.temp_size_in_bytes / 2**30:.3f} total "
          f"{total / 2**30:.3f} GiB; pool {pool_bytes / 2**30:.3f} states "
          f"{state_bytes / 2**30:.3f}")
    assert total < USABLE_BYTES, total / 2 ** 30
    # no state-sized temporary (3.06 GiB); the decode program's are 0.1
    # GiB, the prefill program's 2.3: its attention's float32 scores of
    # a chunk over a whole context's view, (2, 16, 512, 4096), a few at
    # a time (the chunk path of kernels/paged_attention.py, as every
    # served family's)
    assert ma.temp_size_in_bytes < (2 ** 29 if which == "decode"
                                    else 5 * 2 ** 29), \
        ma.temp_size_in_bytes / 2 ** 30
    text = compiled.as_text()
    # an expert's input matrix is read where it lies: no copy of a
    # layer's stack (0.3 GiB) to another layout
    assert "bf16[32,2688,1856]" not in text
    big = states[0][0]
    for op, ln in _pool_sized_results(compiled, big):
        # the state's buffer only ever passes through: the kernel's
        # aliased result, or a prefill chunk's one slot set in place; the
        # pool (larger still) has its rows scattered into it
        of_pool = " bf16[" in ln.split("(")[0]
        assert op in ("parameter", "bitcast", "tuple", "get-tuple-element",
                      "fusion") + (("scatter",) if of_pool else (
                          "custom-call", "dynamic-update-slice")), ln[:300]
        if op == "fusion":
            assert (" scatter(" if of_pool else "dynamic-update-slice(") \
                in _called_computation(text, ln), ln[:300]
    calls = _kernel_calls(compiled)
    assert sum("routed_experts" in c for c in calls) == 2 * 5, calls
    assert sum("ssm_state_update" in c for c in calls) \
        == (6 if which == "decode" else 0), calls
    assert sum("paged_attention_decode" in c for c in calls) \
        == (2 if which == "decode" else 0), calls
