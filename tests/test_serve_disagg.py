"""Serve v2: disaggregated prefill/decode + batched speculative
decoding.  The load-bearing claims, pinned on cpu: the streamed KV
handoff's continuation is BITWISE the unified engine's continuation
(fp32 and int8 pools alike), chaos mid-handoff either retries cleanly
(injected failure) or leaves manifest-less debris the loader rejects
(kill), forced decode-side preemption recomputes to the same tokens,
the self-draft speculative arm commits >= 2 tokens per sequence per
tick without leaving the bucket grid (recompile-free ragged
acceptance), and the phase-split planner sends HBM-bandwidth-rich
members to decode."""
import os

import numpy as np
import pytest

import jax.numpy as jnp

from apex_tpu import nn
from apex_tpu.inference import make_self_draft
from apex_tpu.inference.session import DecodeSession
from apex_tpu.models.gpt import GptModel
from apex_tpu.observe import registry as obs
from apex_tpu.parallel import plan_serve_phase_split
from apex_tpu.runtime import chaos
from apex_tpu.runtime import step_cache as sc
from apex_tpu.runtime.resilience import (CheckpointCorruptError,
                                         CheckpointReshardError,
                                         discard_kv_handoff,
                                         load_kv_handoff,
                                         stream_kv_handoff)
from apex_tpu.serve import (DisaggregatedEngine, Request, ServeEngine,
                            bucket)
from apex_tpu.serve.pool import init_pool_buffer

pytestmark = pytest.mark.serve

PROMPTS = [[5, 9, 11, 3], [7, 2], [1, 2, 3, 4, 5, 6, 7, 8, 9],
           [12, 30, 4]]
MAX_NEW = 6


@pytest.fixture(scope="module")
def model():
    nn.manual_seed(6)
    m = GptModel(vocab_size=73, hidden=32, layers=2, heads=4,
                 max_positions=96, dropout=0.0, attn_dropout=0.0)
    m.eval()
    return m


def _reqs():
    return [Request(f"r{i}", p, MAX_NEW) for i, p in enumerate(PROMPTS)]


def _unified_out(model, cache_dtype=None):
    eng = ServeEngine(model, num_blocks=64, block_size=8, max_batch=4,
                      prefill_chunk=4, cache_dtype=cache_dtype)
    out = eng.run(_reqs())
    eng.block_pool.check_no_leaks()
    return out


def _disagg(model, tmp_path, **kw):
    return DisaggregatedEngine(
        model, num_blocks=64, block_size=8, max_batch=4,
        prefill_chunk=4, handoff_dir=str(tmp_path), **kw)


def _check_disagg(eng):
    eng.prefill.block_pool.check_no_leaks()
    eng.decode.block_pool.check_no_leaks()
    assert not eng.pending


# ---------------------------------------------------------------------------
# handoff bitwise parity: prefill-on-A -> streamed KV -> decode-on-B
# ---------------------------------------------------------------------------


def test_disagg_parity_fp32(model, tmp_path):
    base = _unified_out(model)
    eng = _disagg(model, tmp_path)
    out = eng.run(_reqs())
    assert out == base                    # bitwise greedy parity
    m = eng.metrics()["handoff"]
    assert m["count"] == len(PROMPTS) and m["retries"] == 0
    # one fp32 block of the tiny GPT: 2 layers x K+V x 4 heads x 8 x 8
    assert 0 < m["bytes_peak_host"] <= 2 * 2 * 4 * 8 * 8 * 4
    _check_disagg(eng)


def test_disagg_parity_int8(model, tmp_path):
    base = _unified_out(model, cache_dtype="int8")
    eng = _disagg(model, tmp_path, cache_dtype="int8")
    out = eng.run(_reqs())
    assert out == base
    # int8 handoff streams q and scale as separate parts; the peak is
    # still one single-part block buffer, never a gathered pool
    assert 0 < eng.metrics()["handoff"]["bytes_peak_host"] \
        <= 2 * 2 * 4 * 8 * 8
    _check_disagg(eng)


def test_disagg_open_loop_arrivals_parity(model, tmp_path):
    base = _unified_out(model)
    eng = _disagg(model, tmp_path)
    out = eng.run(_reqs(), arrivals=[0, 2, 3, 7])
    assert out == base
    _check_disagg(eng)


# ---------------------------------------------------------------------------
# speculative decoding: ragged acceptance, recompile-free, >= 2 tok/tick
# ---------------------------------------------------------------------------


def test_unified_spec_parity_and_recompile_free(model):
    base = _unified_out(model)
    sc.reset_stats()
    sc.clear()
    eng = ServeEngine(model, num_blocks=128, block_size=8, max_batch=4,
                      prefill_chunk=4, draft=make_self_draft(model),
                      spec_k=3)
    out = eng.run(_reqs())
    assert out == base                    # spec is exact for ANY draft
    eng.block_pool.check_no_leaks()
    spec = eng.metrics()["spec"]
    assert spec["ticks"] > 0
    # SELF-draft: full acceptance up to finish-truncation (a sequence
    # that completes mid-tick forfeits the rest of its offered window,
    # so the rate lands at exactly 0.5 on this short trace) -> the
    # per-sequence committed-tokens floor the ISSUE pins
    assert spec["accept_rate"] >= 0.5
    seq_ticks = spec["offered"] / 3
    assert spec["committed_tokens"] / seq_ticks >= 2.0
    # ragged acceptance never reaches program identity: verify-step
    # compiles stay within batch x target-table x draft-table buckets
    stats = sc.kind_stats("spec_verify_step")
    bound = (len({bucket(b, 4) for b in range(1, 5)})
             * len({bucket(t) for t in range(1, 5)}) ** 2)
    assert 1 <= stats["compiles"] <= bound
    assert stats["dispatches"] >= stats["compiles"]


def test_disagg_spec_parity_int8_draft(model, tmp_path):
    base = _unified_out(model)
    eng = _disagg(model, tmp_path, draft=make_self_draft(model),
                  spec_k=3, decode_blocks=128,
                  draft_cache_dtype="int8")
    out = eng.run(_reqs())
    assert out == base
    spec = eng.decode.metrics()["spec"]
    assert spec["accept_rate"] >= 0.5
    _check_disagg(eng)


def test_spec_telemetry_names(model):
    reg = obs.get_registry()
    hist0 = reg.histogram("serve.spec.accepted_tokens").count
    eng = ServeEngine(model, num_blocks=128, block_size=8, max_batch=4,
                      prefill_chunk=4, draft=make_self_draft(model),
                      spec_k=2)
    eng.run(_reqs())
    assert reg.histogram("serve.spec.accepted_tokens").count > hist0
    rate = reg.gauge("serve.spec.accept_rate").value
    assert rate is not None and 0.0 <= rate <= 1.0
    eng.block_pool.check_no_leaks()


def test_divergent_draft_still_exact(model):
    """A draft that disagrees with the target (different init) can only
    slow decoding down — never change the emitted tokens."""
    base = _unified_out(model)
    nn.manual_seed(7)
    draft = GptModel(vocab_size=73, hidden=16, layers=1, heads=2,
                     max_positions=96, dropout=0.0, attn_dropout=0.0)
    draft.eval()
    eng = ServeEngine(model, num_blocks=128, block_size=8, max_batch=4,
                      prefill_chunk=4, draft=draft, spec_k=2)
    out = eng.run(_reqs())
    assert out == base
    eng.block_pool.check_no_leaks()


# ---------------------------------------------------------------------------
# chaos mid-handoff + forced preemption
# ---------------------------------------------------------------------------


def test_chaos_mid_handoff_retries_then_parity(model, tmp_path):
    base = _unified_out(model)
    r0 = obs.counter("serve.handoff.retries").value
    with chaos.session(seed=0) as c:
        c.on("serve.kv_handoff", action="fail", at=1)
        eng = _disagg(model, tmp_path)
        out = eng.run(_reqs())
        assert [p for p, _, _ in c.log] == ["serve.kv_handoff"]
    assert out == base                    # retry re-streams, bitwise
    assert obs.counter("serve.handoff.retries").value == r0 + 1
    assert eng.metrics()["handoff"]["retries"] >= 1
    _check_disagg(eng)


def test_chaos_kill_mid_handoff_leaves_rejectable_debris(tmp_path):
    pool = init_pool_buffer(2, 4, 8, 8, 8)
    pool = pool.at[:, :, 1:4].set(1.5)
    d = str(tmp_path / "killed")
    with chaos.session(seed=0) as c:
        c.on("serve.kv_handoff", action="kill", at=2)
        with pytest.raises(chaos.ChaosKilled):
            stream_kv_handoff(d, pool, [1, 2, 3])
    # kill before the manifest commit: debris, no manifest — the
    # loader must refuse it as corrupt, never scatter partial blocks
    assert os.path.exists(d)
    assert "KV_MANIFEST.pkl" not in os.listdir(d)
    with pytest.raises(CheckpointCorruptError):
        load_kv_handoff(d, init_pool_buffer(2, 4, 8, 8, 8), [4, 5, 6])
    discard_kv_handoff(d)
    assert not os.path.exists(d)


def test_forced_preemption_on_decode_engine_parity(model, tmp_path):
    """A decode pool too small for the live set forces preemption after
    the handoff; recompute on the decode engine reproduces the exact
    greedy continuation."""
    reqs = [Request(f"p{i}", [3 + i, 5, 7], 8) for i in range(6)]
    p0 = obs.counter("serve.preemptions").value
    eng = DisaggregatedEngine(model, num_blocks=64, block_size=4,
                              max_batch=4, prefill_chunk=4,
                              decode_blocks=9,
                              handoff_dir=str(tmp_path))
    out = eng.run(reqs)
    assert sorted(out) == [f"p{i}" for i in range(6)]
    assert obs.counter("serve.preemptions").value > p0
    s = DecodeSession(model, batch=1)
    s.append(jnp.asarray([[3, 5, 7]], jnp.int32))
    assert out["p0"] == [int(t) for t in np.asarray(s.generate(8))[0]]
    _check_disagg(eng)


# ---------------------------------------------------------------------------
# load_kv_handoff error classes
# ---------------------------------------------------------------------------


def _streamed(tmp_path, name="h"):
    pool = init_pool_buffer(2, 4, 8, 8, 8)
    pool = pool.at[:, :, 1:4].set(2.25)
    d = str(tmp_path / name)
    manifest, peak = stream_kv_handoff(d, pool, [1, 2, 3])
    return pool, d, manifest, peak


def test_kv_handoff_roundtrip_bitwise(tmp_path):
    pool, d, manifest, peak = _streamed(tmp_path)
    assert manifest["n_blocks"] == 3 and not manifest["quant"]
    assert peak == 2 * 2 * 4 * 8 * 8 * 4   # ONE block's bytes, fp32
    dst, peak2 = load_kv_handoff(
        d, init_pool_buffer(2, 4, 8, 8, 8), [5, 6, 7])
    assert peak2 == peak
    np.testing.assert_array_equal(np.asarray(pool[:, :, [1, 2, 3]]),
                                  np.asarray(dst[:, :, [5, 6, 7]]))
    assert not np.asarray(dst[:, :, [1, 2, 3]]).any()


def test_kv_handoff_int8_roundtrip_bitwise(tmp_path):
    pool = init_pool_buffer(2, 4, 8, 8, 8, dtype="int8")
    pool = type(pool)(pool.q.at[:, :, 1:3].set(7),
                      pool.scale.at[:, :, 1:3].set(0.125))
    d = str(tmp_path / "q")
    manifest, _ = stream_kv_handoff(d, pool, [1, 2])
    assert manifest["quant"]
    dst, _ = load_kv_handoff(
        d, init_pool_buffer(2, 4, 8, 8, 8, dtype="int8"), [3, 4])
    np.testing.assert_array_equal(np.asarray(pool.q[:, :, [1, 2]]),
                                  np.asarray(dst.q[:, :, [3, 4]]))
    np.testing.assert_array_equal(np.asarray(pool.scale[:, :, [1, 2]]),
                                  np.asarray(dst.scale[:, :, [3, 4]]))


def test_kv_handoff_crc_failure_is_corrupt(tmp_path):
    _, d, manifest, _ = _streamed(tmp_path)
    fname = manifest["blocks"][1]["kv"]["file"]
    path = os.path.join(d, fname)
    raw = bytearray(open(path, "rb").read())
    raw[0] ^= 0xFF
    with open(path, "wb") as f:
        f.write(raw)
    with pytest.raises(CheckpointCorruptError, match="checksum"):
        load_kv_handoff(d, init_pool_buffer(2, 4, 8, 8, 8), [4, 5, 6])


def test_kv_handoff_missing_block_is_corrupt(tmp_path):
    _, d, manifest, _ = _streamed(tmp_path)
    os.remove(os.path.join(d, manifest["blocks"][2]["kv"]["file"]))
    with pytest.raises(CheckpointCorruptError):
        load_kv_handoff(d, init_pool_buffer(2, 4, 8, 8, 8), [4, 5, 6])


def test_kv_handoff_geometry_and_count_mismatch_is_reshard(tmp_path):
    _, d, _, _ = _streamed(tmp_path)
    # quantization mismatch: fp32 handoff into an int8 pool
    with pytest.raises(CheckpointReshardError):
        load_kv_handoff(d, init_pool_buffer(2, 4, 8, 8, 8,
                                            dtype="int8"), [4, 5, 6])
    # per-block shape mismatch: different head_dim
    with pytest.raises(CheckpointReshardError):
        load_kv_handoff(d, init_pool_buffer(2, 4, 4, 8, 8), [4, 5, 6])
    # block-count mismatch: a grant that disagrees with the manifest
    with pytest.raises(CheckpointReshardError):
        load_kv_handoff(d, init_pool_buffer(2, 4, 8, 8, 8), [4, 5])


def test_kv_handoff_missing_manifest_is_corrupt(tmp_path):
    with pytest.raises(CheckpointCorruptError, match="manifest"):
        load_kv_handoff(str(tmp_path / "nope"),
                        init_pool_buffer(2, 4, 8, 8, 8), [1])


# ---------------------------------------------------------------------------
# phase-split planner + admission validation
# ---------------------------------------------------------------------------


def test_plan_serve_phase_split_colocates_single_device():
    sp = plan_serve_phase_split()
    assert sp.colocated and sp.prefill == (0,) and sp.decode == (0,)
    assert sp.name() == "colocated"


def test_plan_serve_phase_split_ranks_bandwidth_to_decode():
    # v4 has more HBM bandwidth per sustained FLOP than v5e, so in a
    # mixed fleet the v4 members (indices 2, 3) take decode
    sp = plan_serve_phase_split("v5e:2+v4:2")
    assert not sp.colocated
    assert sp.decode == (2, 3) and sp.prefill == (0, 1)
    assert sp.name() == "prefill:2+decode:2"
    # skewed demand: prefill-heavy traffic shrinks decode to its
    # 1-device floor, still the best-bandwidth member
    sp = plan_serve_phase_split("v5e:2+v4:2", prefill_weight=3.0,
                                decode_weight=1.0)
    assert len(sp.decode) == 1 and sp.decode[0] in (2, 3)
    assert len(sp.prefill) == 3


def test_disagg_submit_rejects_never_fit(model, tmp_path):
    eng = _disagg(model, tmp_path, draft=make_self_draft(model),
                  spec_k=4, decode_blocks=128)
    with pytest.raises(ValueError):
        eng.submit(Request("big", list(range(1, 90)), 10))
