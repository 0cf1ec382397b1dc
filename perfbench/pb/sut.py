"""The system under test: with the families' ``model`` functions
(``perfbench/families/``), the one place of the benchmark that imports
``apex_tpu``.  What is the same for every family is here: the compile
cache, ``make_train_step`` with the configuration's recipe,
``ServeEngine`` with the configuration's ``serve`` block, the feed, the
seeded weights put into the family's model, the program's counters.
Everything it measures with lives elsewhere under ``perfbench/``.
"""
from __future__ import annotations

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from . import weights as _weights


def enable_compile_cache():
    """The program's one compile-cache helper: where
    ``JAX_COMPILATION_CACHE_DIR`` is set nothing is set in code, else a
    fixed directory inside the checkout.  Returns its hit/miss
    counter."""
    from apex_tpu import compile_cache
    return compile_cache.enable()


# -- the family's model with the seeded weights in it --------------------------


def program_weights(family, cfg, seed: int, dtype):
    """The seeded weights as the program's parameter list, one jitted
    call on the device."""
    return _weights.make_weights(family, cfg, seed, dtype,
                                 convert=family.to_program(cfg))


def build_model(family, cfg, seed: int, dtype, **kw):
    model = family.model(cfg, **kw)
    params = list(model.parameters())
    vals = program_weights(family, cfg, seed, dtype)
    if len(vals) != len(params):
        raise RuntimeError(
            f"the program's model has {len(params)} parameters, the "
            f"configuration describes {len(vals)}")
    for p, v in zip(params, vals):
        if tuple(p.data.shape) != tuple(v.shape):
            raise RuntimeError(f"parameter shape {p.data.shape} != "
                               f"seeded {v.shape}")
        p.data = v
    return model


# -- training --------------------------------------------------------------


def build_train_step(family, cfg, seed: int, parallel: str, devices):
    """The program's fused step as the configuration's recipe states it
    (``chip_smoke._lm_step``): bf16 compute, FusedAdam, chunked LM
    loss.  ``parallel``: "single", or "dp" for pure data parallelism
    over ``devices`` through the library's own entry
    (``zero_sharding=True, zero_stage=0``: the GSPMD global-view step
    that a ``parallel.auto`` dp plan threads)."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from apex_tpu.contrib.xentropy import make_chunked_lm_loss
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    tr = cfg["train"]
    model = build_model(family, cfg, seed, jnp.float32, output_hidden=True)
    opt = FusedAdam(list(model.parameters()), lr=tr["lr"],
                    betas=tuple(tr["betas"]), eps=tr["eps"],
                    weight_decay=tr["weight_decay"])
    loss_fn = make_chunked_lm_loss(vocab_size=family.vocab(cfg),
                                   padding_idx=-1)
    kw = {}
    mesh = None
    if parallel == "dp":
        mesh = Mesh(np.array(list(devices)), ("data",))
        kw = dict(zero_sharding=True, zero_stage=0, zero_mesh=mesh,
                  zero_axis="data")
    elif parallel != "single":
        raise ValueError(f"unknown parallel mode {parallel!r}")
    step = make_train_step(model, opt, loss_fn, half_dtype=jnp.bfloat16,
                           loss_scale=1.0, **kw)
    return step, mesh


def train_kind(parallel: str) -> str:
    return "zero_train_step" if parallel == "dp" else "train_step"


def train_feed(batches, sharding):
    """The library's own input path: a ``runtime.DataPrefetcher`` over
    host batches (``sharding``: where each batch is put)."""
    from apex_tpu.runtime import DataPrefetcher
    return DataPrefetcher(batches, device=sharding, depth=2)


def master_params(step):
    return list(step.state.master_params)


def adam_first_moment(step):
    """FusedAdam's first-moment slots, in parameter order."""
    opt = step.state.opt_state
    for key in ("exp_avg", "m"):
        if key in opt:
            return list(opt[key])
    raise RuntimeError(f"no Adam first moment in opt_state keys "
                       f"{list(opt)}")


# -- serving ---------------------------------------------------------------


def build_engine(family, cfg, seed: int):
    import jax.numpy as jnp

    from apex_tpu.serve import ServeEngine

    sv = cfg["serve"]
    if sv.get("draft") is not None:
        raise ValueError("the benchmark serves without a draft model")
    dtype = jnp.dtype(sv["weights_dtype"])
    model = build_model(family, cfg, seed, dtype)
    model.eval()
    return ServeEngine(
        model, num_blocks=sv["num_blocks"], block_size=sv["block_size"],
        max_batch=sv["max_batch"], prefill_chunk=sv["prefill_chunk"],
        cache_dtype=jnp.dtype(sv["cache_dtype"]),
        prefix_cache=sv["prefix_cache"])


def publish_weights(eng, family, cfg, seed: int) -> None:
    """Swap another seed's weights into a running engine through its
    own hot-swap entry (no program is rebuilt; the prefix cache is
    flushed)."""
    import jax.numpy as jnp
    vals = program_weights(family, cfg, seed,
                           jnp.dtype(cfg["serve"]["weights_dtype"]))
    eng.publish_weights(vals)


def make_request(rid, prompt, max_new):
    from apex_tpu.serve import Request
    return Request(rid, prompt, int(max_new))


def kind_stats(kind: str) -> dict:
    from apex_tpu.runtime import step_cache
    return dict(step_cache.kind_stats(kind))
