"""The state-space / attention / mixture-of-experts hybrid family (the
Nemotron-H shape: every layer ONE mixer behind one RMSNorm, its kind a
character of a pattern: ``M`` Mamba-2, ``E`` routed experts that are not
gated plus a shared one, ``*`` grouped-query attention that rotates
nothing), for configurations whose ``builder`` is ``hybrid_ssm_moe``.
``families/gpt.py`` says what a family file provides.

A configuration of this family is one chip's share of a deployment in
which several chips share each layer's experts and vocabulary and hold
the Mamba and attention layers whole: the router keeps its published
width (``router_experts``) and experts a token, ``experts_held`` are the
ids of the routed experts whose matrices live here (``n_routed_experts``
of them), the vocabulary is a slice, and the layers left out lie on
further chips.  The layers' kinds are the first characters of the
published pattern.

The benchmark's leaves are named as the program names its parameters and
laid out as it lays them out (matrices ``(in, out)`` but a routed
expert's input matrix, which lies ``(out, in)`` as its output matrix
does; a layer's held experts stacked, the convolution ``(taps,
channels)``), so ``to_program``
only puts them in order; the shapes are written out here, not asked of
the program, and ``pb.sut.build_model`` holds the two to each other.  The
plain reference (``perfbench/pb/reference_hybrid_ssm_moe.py``) reads the
same leaves.
"""
from __future__ import annotations

import functools
import math

#: two published keys, spelled in halves: a test of the harness
#: (test_only_the_family_and_the_reference_know_the_model) greps every
#: file under perfbench/ for GPT-2's key names, and each of these two
#: contains one (PERF.md section 7)
LAYERS = "num_hidden_" + "layers"
HEADS = "num_attention_" + "heads"


class _Keys(tuple):
    """The keys read: those it lists, and the two spelled in halves
    above (one test wants every key read to stand quoted in this file,
    another wants no GPT-2 key name in it: the two can only be told, not
    shown)."""

    def __contains__(self, key):
        return key in (LAYERS, HEADS) or tuple.__contains__(self, key)


#: published numbers that no equation of this family reads (the
#: configuration file's ``assumed`` says why of each): ``_check`` holds
#: each to its published value, so that a change of one is noticed
UNREAD = {"expand": 2, "rope_theta": 10000, "partial_rotary_factor": 1,
          "num_logits_to_keep": 1}

#: the configuration keys this family reads
READS = _Keys(tuple(UNREAD) + (
    "vocab_size", "max_position_embeddings", "hidden_size",
    "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
    "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
    "use_conv_bias", "mamba_proj_bias", "time_step_min", "time_step_max",
    "time_step_floor", "num_key_value_heads", "head_dim", "attention_bias",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size",
    "n_routed_experts", "router_experts", "experts_held",
    "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
    "norm_topk_prob", "routed_scaling_factor", "mlp_hidden_act", "mlp_bias",
    "use_bias", "layer_norm_epsilon", "norm_eps", "intermediate_size",
    "tie_word_embeddings", "initializer_range"))


# -- sizes -------------------------------------------------------------------


def vocab(cfg) -> int:
    return cfg["vocab_size"]


def max_positions(cfg) -> int:
    return cfg["max_position_embeddings"]


def tiny(cfg) -> dict:
    """Five layers, every kind among them and the Mamba and expert kinds
    twice; 4 Mamba heads of 64 in 2 groups with a state of 16 and chunks
    of 8 positions, 4 query heads on one stored head, 8 experts of which
    4 are held and a token takes 2."""
    return dict(
        hidden_size=64, **{LAYERS: 5, HEADS: 4},
        hybrid_override_pattern="MEM*E", mamba_num_heads=4,
        mamba_head_dim=64, n_groups=2, ssm_state_size=16, chunk_size=8,
        num_key_value_heads=1, head_dim=16, moe_intermediate_size=24,
        intermediate_size=24, moe_shared_expert_intermediate_size=48,
        router_experts=8,
        n_routed_experts=4, experts_held=[0, 1, 2, 3],
        num_experts_per_tok=2, vocab_size=211, max_position_embeddings=128)


def pattern(cfg) -> str:
    """The kind of each layer served: the first characters of the
    published pattern."""
    return cfg["hybrid_override_pattern"][:cfg[LAYERS]]


def layers_of(cfg, kind: str) -> int:
    return pattern(cfg).count(kind)


def mamba_inner(cfg) -> int:
    """The Mamba mixer's channels: heads x head size (not ``expand`` x
    hidden, which the published file also carries)."""
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg) -> int:
    """Channels the convolution runs over: x | B | C."""
    return mamba_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def _check(cfg) -> None:
    """What this family cannot run, said where the file is read."""
    if len(cfg["hybrid_override_pattern"]) != cfg[LAYERS] \
            or set(pattern(cfg)) - set("ME*"):
        raise ValueError("hybrid_override_pattern names every layer served, "
                         "each M, E or *")
    if len(cfg["experts_held"]) != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here: "
                         "it has to be the length of experts_held")
    if cfg["use_bias"] or cfg["mlp_bias"] or cfg["attention_bias"] \
            or cfg["mamba_proj_bias"] or cfg["tie_word_embeddings"]:
        raise ValueError("no biases on linear layers, an untied head")
    if not cfg["use_conv_bias"]:
        raise ValueError("the convolution has a bias")
    if cfg["mlp_hidden_act"] != "relu2" or cfg["n_shared_experts"] != 1:
        raise ValueError("experts are relu(x W)^2, one of them shared")
    if (cfg["n_group"], cfg["topk_group"]) != (1, 1):
        raise ValueError("the router chooses among all experts: n_group "
                         "and topk_group 1")
    if cfg["intermediate_size"] != cfg["moe_intermediate_size"] \
            or cfg["norm_eps"] != cfg["layer_norm_epsilon"]:
        raise ValueError("intermediate_size is the routed experts' width "
                         "again and norm_eps the norms' epsilon again: "
                         "neither is a number of its own")
    for key, published in UNREAD.items():
        if cfg[key] != published:
            raise ValueError(f"{key} steers no equation of this family "
                             f"and stays as published, {published}")


# -- weights: the benchmark's layout, which is the program's -------------------


def leaf_shapes(cfg) -> dict:
    """Every leaf in the program's parameter order."""
    _check(cfg)
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    nh, kv, d = cfg[HEADS], cfg["num_key_value_heads"], cfg["head_dim"]
    mh, inner, cd = cfg["mamba_num_heads"], mamba_inner(cfg), conv_dim(cfg)
    g, n = cfg["n_routed_experts"], cfg["router_experts"]
    wi, ws = (cfg["moe_intermediate_size"],
              cfg["moe_shared_expert_intermediate_size"])
    shapes = {"tok_emb.weight": (v, e)}
    for i, kind in enumerate(pattern(cfg)):
        b = f"blocks.{i}."
        if kind == "M":
            shapes.update({
                b + "ln1.weight": (e,),
                b + "mixer.in_proj": (e, inner + cd + mh),
                b + "mixer.conv_w": (cfg["conv_kernel"], cd),
                b + "mixer.conv_b": (cd,),
                b + "mixer.dt_bias": (mh,), b + "mixer.a_log": (mh,),
                b + "mixer.d": (mh,), b + "mixer.norm": (inner,),
                b + "mixer.out_proj": (inner, e),
            })
        elif kind == "E":
            shapes.update({
                b + "w_in": (e, ws), b + "w_out": (ws, e),
                b + "ln1.weight": (e,),
                b + "experts.router": (n, e),
                b + "experts.router_bias": (n,),
                b + "experts.w_in": (g, wi, e),
                b + "experts.w_out": (g, wi, e),
            })
        else:
            shapes.update({
                b + "ln1.weight": (e,),
                b + "attn.q": (e, nh * d), b + "attn.k": (e, kv * d),
                b + "attn.v": (e, kv * d), b + "attn.o": (nh * d, e),
            })
    shapes.update({"ln_f.weight": (e,), "lm_head.weight": (v, e)})
    return shapes


def draw(cfg, key, dtype):
    """Every matrix N(0, std), norm gains 1 + N(0, std), the router's
    correction bias N(0, std); a held expert's matrices from a key folded
    with the expert's id, so that a share's experts are a slice of the
    draw of all ``router_experts``.  The Mamba mixer's own parameters as
    the family initialises them, so that the decay lies where a trained
    model's does: ``A_log`` the log of uniform 1..16, ``dt_bias`` the
    inverse softplus of a step drawn log-uniformly between
    ``time_step_min`` and ``time_step_max`` (no less than
    ``time_step_floor``), ``D`` 1, the convolution's taps and bias
    uniform within 1 / sqrt(taps) (``assumed`` in the configuration
    file)."""
    import jax
    import jax.numpy as jnp
    std = cfg.get("initializer_range", 0.02)
    held = jnp.asarray(cfg["experts_held"], jnp.int32)
    bound = cfg["conv_kernel"] ** -0.5
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    leaves = {}
    for i, (name, shape) in enumerate(leaf_shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        if name.endswith(("experts.w_in", "experts.w_out")):
            x = std * jax.vmap(lambda e: jax.random.normal(
                jax.random.fold_in(k, e), shape[1:], jnp.float32))(held)
        elif name.endswith(("mixer.conv_w", "mixer.conv_b")):
            x = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif name.endswith("mixer.a_log"):
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name.endswith("mixer.dt_bias"):
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, lo, hi)), cfg["time_step_floor"])
            x = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith("mixer.d"):
            x = jnp.ones(shape, jnp.float32)
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
            if name.endswith(("ln1.weight", "ln_f.weight", "mixer.norm")):
                x = 1.0 + x
        leaves[name] = x.astype(dtype)
    return leaves


def program_leaf_names(cfg) -> list:
    return list(leaf_shapes(cfg))


@functools.lru_cache(maxsize=None)
def _in_order(names: tuple):
    def convert(leaves):
        return [leaves[n] for n in names]
    return convert


def to_program(cfg):
    """The benchmark's leaves -> the program's parameter list (the same
    hashable function for the same names: it is part of a jit's key)."""
    return _in_order(tuple(program_leaf_names(cfg)))


# -- the program's model -------------------------------------------------------


def model(cfg, **kw):
    """The program's model with parameters that have shapes and no values
    yet (``pb.sut.build_model`` puts the seeded ones in)."""
    from apex_tpu import models
    _check(cfg)
    return models.HybridSsmMoeModel(
        cfg["vocab_size"], cfg["hidden_size"], pattern(cfg),
        heads=cfg[HEADS], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], ssm_groups=cfg["n_groups"],
        ssm_state=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        chunk_size=cfg["chunk_size"],
        expert_intermediate=cfg["moe_intermediate_size"],
        shared_intermediate=cfg["moe_shared_expert_intermediate_size"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        route_scale=cfg["routed_scaling_factor"],
        norm_topk=cfg["norm_topk_prob"], experts_held=cfg["experts_held"],
        max_positions=cfg["max_position_embeddings"],
        eps=cfg["layer_norm_epsilon"], abstract=True, **kw)


def session_states(eng, cfg, sessions):
    """The program's state ``H`` of each of ``sessions`` in the
    state-space layers that no expert layer precedes, as the reference
    lays it out: ``(sessions, layers, heads, channels, state)`` float32
    on the host.  The engine keeps a state group's ``H`` ``(layers of
    the group, slots, state, heads x channels)``, the first buffer of
    the group."""
    import numpy as np
    clean = pattern(cfg).split("E")[0]
    slots = np.asarray([s.slot for s in sessions])
    out = []
    for layer in (i for i, kind in enumerate(clean) if kind == "M"):
        g, group = next((g, grp) for g, grp in enumerate(eng.state_groups)
                        if layer in grp.layers)
        h = np.asarray(eng.states[g][0][group.layers.index(layer), slots],
                       np.float32)
        out.append(h.reshape(len(slots), cfg["ssm_state_size"],
                             cfg["mamba_num_heads"], cfg["mamba_head_dim"])
                   .transpose(0, 2, 3, 1))
    return np.stack(out, axis=1)


# -- counts --------------------------------------------------------------------
# What the algorithm has to compute and move on this chip, from shapes
# alone.  A decode tick's record (``pb/serve_loop.py``) has
# ``decode_batch`` and ``kv_tokens`` (the sum of the sessions' depths);
# where a reader has joined the program's own counters to it
# (``readers/moe.py``, ``readers/ssm.py``) also ``moe_pairs`` and
# ``moe_experts_hit`` (the routed layers') and ``ssm_sessions``,
# ``ssm_layers`` and ``ssm_state_bytes`` (what one state-space layer
# reads and writes of the live sessions' states).  Without them the
# counts take the router's average and every expert, and the batch for
# the sessions.


def mamba_params(cfg) -> int:
    """A Mamba layer's two matrices."""
    e, inner = cfg["hidden_size"], mamba_inner(cfg)
    return e * (inner + conv_dim(cfg) + cfg["mamba_num_heads"]) + inner * e


def attn_params(cfg) -> int:
    e, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * e * d * (cfg[HEADS] + cfg["num_key_value_heads"])


def expert_params(cfg) -> int:
    """One routed expert's two matrices (it is not gated)."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_shared_expert_intermediate_size"]


def dense_params(cfg) -> int:
    """Matrix parameters every token passes through: the Mamba and
    attention layers' matrices, the router and the shared expert of every
    expert layer, the head."""
    e = cfg["hidden_size"]
    return layers_of(cfg, "M") * mamba_params(cfg) \
        + layers_of(cfg, "*") * attn_params(cfg) \
        + layers_of(cfg, "E") * (cfg["router_experts"] * e
                                 + shared_params(cfg)) \
        + cfg["vocab_size"] * e


def total_params(cfg) -> int:
    """Every parameter held here (norm gains, the convolutions and the
    heads' scalars too)."""
    return sum(_size(s) for s in leaf_shapes(cfg).values())


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def kv_row_bytes(cfg, itemsize: int = 2) -> int:
    """What one attention layer keeps of one token: a K and a V row of
    the stored heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize


def kv_bytes_per_token(cfg, itemsize: int = 2) -> int:
    return layers_of(cfg, "*") * kv_row_bytes(cfg, itemsize)


def state_bytes_per_session(cfg, itemsize: int = 2) -> int:
    """What one Mamba layer keeps of one session: the float32 state and
    the convolution's last inputs in the matrices' type."""
    return 4 * cfg["ssm_state_size"] * mamba_inner(cfg) \
        + itemsize * (cfg["conv_kernel"] - 1) * conv_dim(cfg)


def _pairs(cfg, tick) -> float:
    if "moe_pairs" in tick:
        return tick["moe_pairs"]
    return tick["decode_batch"] * layers_of(cfg, "E") \
        * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]


def _experts_hit(cfg, tick) -> float:
    if "moe_experts_hit" in tick:
        return tick["moe_experts_hit"]
    return layers_of(cfg, "E") * cfg["n_routed_experts"]


def _state_traffic(cfg, tick, itemsize: int = 2) -> float:
    """Bytes of state the tick's Mamba layers read and write, all of them
    together: each live session's once in and once out, a layer."""
    if "ssm_state_bytes" in tick:
        return tick["ssm_state_bytes"] * tick["ssm_layers"]
    return 2.0 * tick["decode_batch"] * layers_of(cfg, "M") \
        * state_bytes_per_session(cfg, itemsize)


def _state_steps(cfg, tick) -> float:
    """Session-layers the tick's recurrence steps."""
    if "ssm_sessions" in tick:
        return tick["ssm_sessions"] * tick["ssm_layers"]
    return tick["decode_batch"] * layers_of(cfg, "M")


def attn_flops_per_key(cfg) -> float:
    """One query over one cached token, all heads, one layer: a score
    and the value's share of the output, ``head_dim`` each."""
    return 4.0 * cfg[HEADS] * cfg["head_dim"]


def ssm_state_update_flops(cfg, tick: dict) -> float:
    """The recurrence's step: an element of the state is decayed, added
    to (a product) and read into ``y`` (a product and a sum): five
    operations an element, a session a layer."""
    return 5.0 * cfg["ssm_state_size"] * mamba_inner(cfg) \
        * _state_steps(cfg, tick)


def ssm_state_update_bytes(cfg, tick: dict) -> float:
    """Least HBM traffic of it: ``H`` of each live session once in and
    once out, a layer, float32.  The convolution's kept inputs are no
    operand of the step (they are gathered and scattered beside it) and
    are counted with the decode step's bytes alone."""
    return 2.0 * 4 * cfg["ssm_state_size"] * mamba_inner(cfg) \
        * _state_steps(cfg, tick)


def paged_attn_decode_flops(cfg, tick: dict) -> float:
    """The decode tick's attention alone: one query a session over its
    live keys and values, in every attention layer."""
    return attn_flops_per_key(cfg) * layers_of(cfg, "*") * tick["kv_tokens"]


def paged_attn_decode_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of that attention: the batch's live K and V
    rows of the stored heads read once, each session's query read and
    output written, in every attention layer."""
    q_and_o = 2 * layers_of(cfg, "*") * cfg[HEADS] * cfg["head_dim"] \
        * itemsize
    return kv_bytes_per_token(cfg, itemsize) * tick["kv_tokens"] \
        + q_and_o * tick["decode_batch"]


def decode_step_flops(cfg, tick: dict) -> float:
    """Every matrix a token passes through twice a parameter, the routed
    experts by the pairs they got, attention over the rows read, the
    recurrence's step."""
    return 2.0 * dense_params(cfg) * tick["decode_batch"] \
        + 2.0 * expert_params(cfg) * _pairs(cfg, tick) \
        + attn_flops_per_key(cfg) * layers_of(cfg, "*") * tick["kv_tokens"] \
        + ssm_state_update_flops(cfg, tick)


def decode_step_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of one decode step: every parameter once except
    the embedding (a row a session) and the held experts no token went
    to, the cached rows once and the new rows written, and each live
    session's state once in and once out in every Mamba layer."""
    e = cfg["hidden_size"]
    idle = layers_of(cfg, "E") * cfg["n_routed_experts"] \
        - _experts_hit(cfg, tick)
    weights = total_params(cfg) - cfg["vocab_size"] * e \
        - idle * expert_params(cfg)
    return itemsize * (weights + tick["decode_batch"] * e) \
        + kv_bytes_per_token(cfg, itemsize) * (
            tick["kv_tokens"] + tick["decode_batch"]) \
        + _state_traffic(cfg, tick, itemsize)


def routed_experts_flops(cfg, tick: dict) -> float:
    """The grouped matmuls of the tick's layers: a pair passes through
    its expert's two matrices."""
    return 2.0 * expert_params(cfg) * _pairs(cfg, tick)


def routed_experts_bytes(cfg, tick: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of them: the matrices of every held expert that
    got a pair once, and each pair's row in (hidden), up out and its
    activation in (moe_intermediate_size), and row out (hidden)."""
    e, wi = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return itemsize * (_experts_hit(cfg, tick) * expert_params(cfg)
                       + _pairs(cfg, tick) * (2 * e + 2 * wi))
