"""The cells' step programs compiled for a described TPU v5e (2x2) at
the cells' real shapes: the engine settings and batch sizes of the
configuration files are checked against the chip's memory without chip
time.  Nothing runs; a compile that passes is not a chip run.

The topology is described inside a fixture, never at import, and all of
these live in one file (``on-chip-measurement`` guide, section 2).
"""
import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import pb_tiny  # noqa: F401  (puts perfbench/ on sys.path)
from pb import cells, sut

pytestmark = pytest.mark.kernels

#: what the compiler allows a program on one v5e chip (16 GiB less the
#: runtime's reserve): its own refusal message says "15.75G"
USABLE_BYTES = int(15.75 * 2 ** 30)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for an unattached chip cannot be read back from the
    # persistent cache: keep it off around these
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _cell(name):
    return cells.Cell(name)


def _total(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes), ma


def _shaped(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compiled_mode():
    from apex_tpu.kernels.dispatch import force_mode
    return force_mode("compiled")      # what pallas_mode() says on the chip


def test_train_step_of_gpt2s_train_fits(one_chip):
    cell = _cell("gpt2s-train")
    mix = cell.traffic
    step, _ = sut.build_train_step(cell.family, cell.config, 0, "single",
                                   None)
    ids = jax.ShapeDtypeStruct((mix["global_batch"], mix["seq_len"]),
                               jnp.int32, sharding=one_chip)
    with _compiled_mode():
        compiled = jax.jit(step._raw_step_fn, donate_argnums=(0,)).lower(
            _shaped(step.state, one_chip), ids, ids).compile()
    total, ma = _total(compiled)
    assert total < USABLE_BYTES
    # the state is updated in place, and flash attention is a kernel:
    # 12 layers x (forward + dq + dkv)
    assert ma.alias_size_in_bytes > 1.5 * 2 ** 30
    assert compiled.as_text().count("tpu_custom_call") >= 36
    # at least half of the 25% floor by arguments alone: the cell is
    # not a toy
    assert total > 0.25 * 16e9


def _serve_programs(cell, cfg, one_chip):
    """The engine's decode and prefill programs lowered at their largest
    buckets, and the pool as the engine makes it: the pool's format is
    the program's own (``init_pool_buffer``, as ``ServeEngine.__init__``
    calls it), never spelled out here."""
    from apex_tpu.serve import kernels as serve_kernels
    from apex_tpu.serve.pool import init_pool_buffer
    family = cell.family
    sv = cfg["serve"]
    model = sut.build_model(family, cfg, 0, jnp.dtype(sv["weights_dtype"]))
    model.eval()
    params = list(model.parameters()) + list(model.buffers())
    vals = _shaped([p.data for p in params], one_chip)
    attn = model.blocks[0].attn
    pool = _shaped(jax.eval_shape(lambda: init_pool_buffer(
        len(model.blocks), attn.num_heads, attn.head_dim, sv["num_blocks"],
        sv["block_size"], jnp.dtype(sv["cache_dtype"]))), one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    nb = family.max_positions(cfg) // sv["block_size"]  # a full context
    with _compiled_mode():          # the kernels the chip would take
        decode = jax.jit(serve_kernels.build_decode_fn(
            model, params, sv["block_size"], sv["num_blocks"]),
            donate_argnums=(1,)).lower(
                vals, pool, i32(sv["max_batch"]), i32(sv["max_batch"]),
                i32(sv["max_batch"], nb))
        prefill = jax.jit(serve_kernels.build_prefill_fn(
            model, params, sv["block_size"], sv["num_blocks"]),
            donate_argnums=(1,)).lower(
                vals, pool, i32(1, sv["prefill_chunk"]), i32(1, nb), i32(),
                i32())
    return decode, prefill, pool


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_serve_programs_of_gpt2_medium_fit(one_chip, which):
    """The engine settings in ``gpt2-medium.json`` (every gpt2-medium
    cell shares them): the largest bucket of each program, pool
    donated."""
    cell = _cell("gpt2m-serve-decode")
    cfg = cell.config
    decode, prefill, pool = _serve_programs(cell, cfg, one_chip)
    compiled = (decode if which == "decode" else prefill).compile()
    total, ma = _total(compiled)
    assert total < USABLE_BYTES
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    assert ma.alias_size_in_bytes >= pool_bytes         # updated in place
    sv = cfg["serve"]
    assert sv["kv_bytes_per_token"] == cell.family.kv_bytes_per_token(cfg)
    assert pool_bytes == sv["num_blocks"] * sv["block_size"] \
        * sv["kv_bytes_per_token"]
    assert sv["sessions_of_1024_tokens_in_pool"] == \
        sv["num_blocks"] * sv["block_size"] // 1024
    # the program works on the pool where it lies: its temporaries are a
    # small part of it (0.015 GiB decode, 0.064 prefill: the file's
    # memory_reckoning), where the program before PR 27 kept three
    # copies
    assert ma.temp_size_in_bytes < pool_bytes // 8


def test_max_batch_32_is_refused_as_the_configuration_says(one_chip):
    """Turned round at PR 28: since PR 27 the decode program takes the
    pool where it lies, and batch 32 over 2048 blocks is not refused
    but fits with room to spare (3.68 GiB by the configuration's
    ``memory_reckoning``).  ``max_batch`` 16 is a choice of the cell and
    no limit of the chip."""
    cell = _cell("gpt2m-serve-decode")
    cfg = json.loads(json.dumps(cell.config))
    cfg["serve"]["max_batch"] = 32
    decode, _, pool = _serve_programs(cell, cfg, one_chip)
    total, ma = _total(decode.compile())
    assert total < 4 * 2 ** 30 < USABLE_BYTES
    assert ma.alias_size_in_bytes >= \
        int(np.prod(pool.shape)) * pool.dtype.itemsize


def test_data_parallel_step_over_the_four_chips(topo, monkeypatch):
    """The step of the cell that ISSUE 25 ranks last, 64 x 1024 over
    four chips, built by ``sut.build_train_step(..., "dp", devices)``:
    the library's own entry (``make_train_step(zero_sharding=True,
    zero_stage=0, zero_mesh=...)``, which is also what a
    ``parallel.auto`` dp plan threads), and its own program with the
    shardings it gives it.  At PR 25 the TPU compiler refuses that
    program (a Mosaic kernel cannot be partitioned automatically), so
    the cell is out of BENCHMARK.json (PERF.md section 7); when the
    program is mended this test passes and the cell can come in."""
    cfg = _cell("gpt2s-train").config
    global_batch, seq_len = 64, 1024
    # the described chips are not attached: the entry's placement of
    # its state is skipped, nothing else of it is
    with monkeypatch.context() as m:
        m.setattr(jax, "device_put", lambda x, *a, **k: x)
        step, mesh = sut.build_train_step(_cell("gpt2s-train").family, cfg, 0,
                                          "dp", topo.devices)
    assert type(step).__name__ == "ZeroTrainStep" and mesh.size == 4
    rows = NamedSharding(mesh, P("data"))
    ids = jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32,
                               sharding=rows)
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        step.state, step.shardings)
    try:
        with _compiled_mode():
            compiled = step._jitted((rows, rows)).lower(
                state, ids, ids).compile()
    except NotImplementedError as e:
        assert "Mosaic kernels cannot be automatically partitioned" in str(e)
        pytest.xfail("the library's data-parallel entry cannot carry the "
                     "flash-attention kernel yet: " + str(e))
    total, _ = _total(compiled)
    assert total < USABLE_BYTES
    assert "all-reduce" in compiled.as_text()
