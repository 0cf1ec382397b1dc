"""``flash_attn_kernels_roofline``: the train step's flash kernels found
by their names in the trace, whatever their operands' layout.  Nothing
here needs a device."""
import json
import os

import pytest

import pb_tiny  # noqa: F401  (puts perfbench/ on sys.path)
from pb import cells, peaks

counts = cells.family_module("gpt")

#: a custom call as the trace prints it, on heads split out to
#: (batch x heads, sequence, head size) and on the projection's own layout
SPLIT = "%{}.{} = bf16[192,1024,64] custom-call(bf16[192,1024,64] %p)"
PACKED = "%{}.{} = bf16[16,1024,2304] custom-call(bf16[16,1024,2304] %p)"


def _ctx(ops):
    with open(os.path.join(pb_tiny.BENCH, "configs", "gpt2-small.json")) as f:
        small = json.load(f)
    return {"cfg": small, "family": counts, "trace": {"ops": ops},
            "mix": {"global_batch": 16, "seq_len": 1024},
            "peaks": peaks.peaks_for("TPU v5 lite"),
            "counters": {"steps": 2, "chips": 1}}


def _ops(form, names):
    """Two steps of twelve layers: each kernel of ``names`` 400 us a
    call, and a fusion beside each that does not count."""
    ops = []
    for i in range(24):
        for name in names:
            ops.append((form.format(name, i), 1000 * i, 400_000))
        ops.append((f"%fusion.{i} = bf16[16,1024,768] fusion()", 1000 * i,
                    90_000))
    return ops


@pytest.mark.parametrize("form", [SPLIT, PACKED], ids=["split", "packed"])
@pytest.mark.parametrize("names", [
    ("flash_attn_fwd", "flash_attn_bwd"),
    ("flash_attn_fwd", "flash_attn_bwd_dkv", "flash_attn_bwd_dq"),
], ids=["resident", "tiled"])
def test_flash_kernels_are_read_by_name_in_either_layout(form, names):
    reader, kw = cells.metric_reader("flash_attn_kernels_roofline")
    by_shape, _ = cells.metric_reader("flash_attn_roofline")
    ctx = _ctx(_ops(form, names))
    flops = counts.flash_attn_flops_train(ctx["cfg"], 16, 1024)
    nbytes = counts.flash_attn_bytes_train(ctx["cfg"], 16, 1024)
    least = max(flops / 197e12, nbytes / 819e9)
    want = 100.0 * 2 * least / (24 * len(names) * 400e-6)
    assert 0 < want < 100
    assert reader(ctx, **kw) == pytest.approx(want)
    # the reader by operand shape agrees where it finds the kernels, and
    # reads nothing where no (batch x heads, sequence, head size) exists
    assert by_shape(ctx) == (pytest.approx(want) if form is SPLIT else None)


def test_flash_kernels_by_name_reads_nothing_where_nothing_is():
    """No kernel of those names (an attention of fusions, a parent
    without them), no trace, no peaks (off the chip): nothing, never 0."""
    reader, kw = cells.metric_reader("flash_attn_kernels_roofline")
    ctx = _ctx(_ops(PACKED, ("flash_attn_fwd", "flash_attn_bwd")))
    fusions = [o for o in ctx["trace"]["ops"] if "fusion" in o[0]]
    for gone in ({"trace": {"ops": fusions}}, {"trace": {"ops": []}},
                 {"trace": None}, {"peaks": None}):
        assert reader(dict(ctx, **gone), **kw) is None


def test_flash_kernels_by_name_is_declared_for_the_train_cell():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    new = by_name["flash_attn_kernels_roofline"]
    assert (new["unit"], new["better"], new["source"], new["layer"],
            new["moves"], new["workloads"]) == (
        "%", "higher", "device_trace", "kernel tier", "train_tokens_per_s",
        ["gpt2s-train"])
    assert new in cells.Cell("gpt2s-train").per_layer
