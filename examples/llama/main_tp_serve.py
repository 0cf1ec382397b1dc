"""Tensor-parallel Llama serving example: one set of weights, decoded
across a TP mesh with head-sharded KV caches — the configuration that
lets a model too large for one chip's HBM (e.g. the ``llama_7b``
preset at bf16 + cache) serve across chips.

Demonstrates, on the same weights:
  1. plain TP greedy decode (``generate(..., mesh=...)``) and its
     bit-identity with single-shard decode,
  2. int8 weight-only quantization under TP,
  3. TP-target + replicated-draft speculative decoding
     (``speculative_generate(..., mesh=...)``), greedy-exact,
  4. beam search under the same mesh (``beam_generate(..., mesh=...)``),
     bit-identical to single-shard beam search.

Run (needs ``--tp`` devices; examples/README.md has the virtual-mesh
recipe for a host without that many):
    python main_tp_serve.py --tp 2 --new-tokens 32

The reference repo has no inference path (SURVEY.md §2 — it is a
training-side library); this example exercises the framework's own
serving story end to end.
"""
import argparse
import sys


def parse_args():
    p = argparse.ArgumentParser(description="TP Llama serving demo")
    p.add_argument("--tp", type=int, default=2, help="TP mesh size")
    p.add_argument("--new-tokens", type=int, default=32)
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv-heads", type=int, default=4)
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    import apex_tpu.nn as nn
    from apex_tpu.inference import (beam_generate, quantize_int8,
                                    speculative_generate)
    from apex_tpu.models import LlamaModel, generate

    devs = jax.devices()
    if len(devs) < args.tp:
        sys.exit(f"need {args.tp} devices, have {len(devs)}")
    mesh = Mesh(np.array(devs)[:args.tp].reshape(args.tp), ("tp",))
    print(f"mesh: {args.tp} x {devs[0].platform}")

    vocab = 2048
    max_pos = args.prompt_len + args.new_tokens + 8

    def build(**kw):
        nn.manual_seed(0)
        return LlamaModel(vocab_size=vocab, hidden=args.hidden,
                          layers=args.layers, heads=args.heads,
                          kv_heads=args.kv_heads, max_positions=max_pos,
                          **kw)

    # in production: llama_from_hf(...) then set tp_axis at build time
    # and load the same checkpoint into both — weights are FULL
    # (replicated, sliced at trace time), so checkpoints are
    # mesh-independent
    single = build()
    single.eval()
    tp = build(tp_axis="tp")
    tp.eval()
    for ps, pd in zip(single.parameters(), tp.parameters()):
        pd.data = ps.data

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, vocab,
                                      (1, args.prompt_len)))

    # 1. TP greedy decode, bit-identical to single-shard
    want = np.asarray(generate(single, prompt, args.new_tokens))
    got = np.asarray(generate(tp, prompt, args.new_tokens, mesh=mesh))
    assert (want == got).all(), "TP decode diverged from single-shard"
    print(f"tp greedy decode: {got.shape[1]} tokens, "
          f"bit-identical to single-shard: True")

    # 2. int8 weight-only under TP (per-device cache already KVH/n-wide;
    #    int8 halves the weight reads on top)
    quantize_int8(tp, min_size=1)
    out8 = np.asarray(generate(tp, prompt, args.new_tokens, mesh=mesh))
    print(f"tp int8 decode: {out8.shape[1]} tokens")

    # 3. speculative decoding: TP target + small replicated draft
    nn.manual_seed(1)
    draft = LlamaModel(vocab_size=vocab, hidden=64, layers=1, heads=2,
                       max_positions=max_pos)
    draft.eval()
    spec = np.asarray(speculative_generate(
        tp, draft, prompt, args.new_tokens, k=4, mesh=mesh))
    assert (spec == out8).all(), \
        "speculative decode broke the greedy exactness guarantee"
    print(f"tp speculative decode: exact match with tp int8 decode: True")

    # 4. beam search under the same mesh (int8 weights already applied
    #    to tp; compare against single-shard int8 beams)
    quantize_int8(single, min_size=1)
    bwant = np.asarray(beam_generate(single, prompt, args.new_tokens,
                                     num_beams=3))
    bgot = np.asarray(beam_generate(tp, prompt, args.new_tokens,
                                    num_beams=3, mesh=mesh))
    assert (bwant == bgot).all(), "TP beam search diverged"
    print(f"tp beam search (3 beams): bit-identical to single-shard: "
          f"True")


if __name__ == "__main__":
    main()
