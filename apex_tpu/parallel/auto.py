"""``parallel="auto"`` — an analytical parallelism planner + cost model.

Every parallelism primitive in this framework is a manual knob on
:func:`~apex_tpu.training.make_train_step` (``axis_name``, ``tp_axis``,
``zero_sharding``/``zero_stage``, ``accum_steps``) or a model build option
(``tp_axis=``, ``sp_axis=``, the chunked LM loss).  Picking the
configuration is worth double-digit throughput (unledgered run, round 5:
+13–15% from the chunked vocab chain alone, batch-size plateaus that
invert per model), and the AMP (arXiv:2210.07297) / Galvatron
(arXiv:2504.03662) line of work shows an analytical cost model over
(compute FLOPs, collective bytes, memory footprint) ranks parallel plans
reliably without exhaustive on-device search.  This module is that brain:

1. **enumerate** candidate plans — mesh factorizations dp × sp × tp, ZeRO
   stage 0/1/3, gradient-accumulation K, chunked-loss on/off;
2. **prune** memory-infeasible ones with an explicit HBM model (masters +
   optimizer slots under the chosen ZeRO stage + half model copies +
   gradient carry + activation peak under accumulation + the vocab-logits
   working set vs the chunked-loss lever) — every rejection carries a
   stated reason, nothing is pruned silently;
3. **rank** the survivors with a roofline step-time model: per-device
   FLOPs at the chip's derated peak, HBM bytes at its bandwidth, and
   ring-model ICI time for every collective the plan will emit (psum /
   reduce-scatter / all-gather / ppermute on the candidate mesh axes);
4. **return** a :class:`Plan` whose ``describe()`` prints the predicted
   ms/step, predicted HBM breakdown, the collectives it emits, and — via
   :meth:`PlanReport.describe` — why rejected plans lost.

The planner is pure host-side Python over static shapes.  Its model
constants come from two places: the per-model FLOP/activation profile is
measured from XLA's own cost analysis (``lower().cost_analysis()`` /
``compile().memory_analysis()`` of the unsharded forward+backward at two
probe batch sizes, linearly fitted), and the per-chip constants (peak
FLOP/s, HBM bytes/bandwidth, ICI bandwidth/latency) live in the
:data:`CHIPS` table, checked against ``bench.py --plan``'s
predicted-vs-measured output.

The planner only *drives* primitives that already exist and are tested:
dp/ZeRO plans run through the GSPMD global-view path
(:class:`~apex_tpu.parallel.zero.ZeroTrainStep`, stage 0 = replicated
state / pure data parallelism), tp/sp plans through the
``shard_map``-wrapped explicit-axis path — there are no new execution
paths, and the step-program cache keys carry the plan so cache stats stay
per-plan observables.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..observe import registry as _obs

#: per-wrap token in the step-program cache key — two planned steps with
#: identical signatures close over different model/optimizer objects
_PLAN_TOKENS = itertools.count()


# ---------------------------------------------------------------------------
# Chip constants (the calibration table — see docs/auto_parallel.md)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-device hardware constants the cost model prices against.

    ``efficiency`` derates the spec-sheet peak to the sustained fraction a
    well-tuned fused step reaches (the bench-measured MFU band, not the
    marketing number).  ``shared_host=True`` marks *virtual* devices
    (``--xla_force_host_platform_device_count``): they split one host's
    cores and memory bus, so spreading work over more of them never buys
    compute time — only memory-model wins — and every collective is a
    host memcpy.  That inversion is deliberate: on the CPU test mesh the
    planner must predict the order a CPU measurement produces.
    """
    name: str
    peak_flops: float        # per device (bf16/fp16 ALU peak, FLOP/s)
    hbm_bytes: float         # per device
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s per link direction
    ici_latency_s: float     # per-hop collective latency
    overhead_s: float        # fixed per-microbatch dispatch/loop overhead
    efficiency: float = 0.45
    shared_host: bool = False
    #: host↔device transfer bandwidth (PCIe/DMA), the prior the offload
    #: term prices against when the executor has no measured H2D rate
    h2d_bw: float = 16e9

    def sustained_flops(self) -> float:
        return self.peak_flops * self.efficiency

    def scaled(self, factor: float) -> "ChipSpec":
        """A speed-scaled copy (compute, HBM and ICI bandwidth all
        multiplied by ``factor``) — the fleet syntax's straggler
        stand-in, e.g. ``"cpu*0.5"`` is a host running at half speed."""
        if factor <= 0:
            raise ValueError(f"scale factor must be > 0, got {factor}")
        if factor == 1.0:
            return self
        return dataclasses.replace(
            self, name=f"{self.name}*{factor:g}",
            peak_flops=self.peak_flops * factor,
            hbm_bw=self.hbm_bw * factor,
            ici_bw=self.ici_bw * factor,
            h2d_bw=self.h2d_bw * factor)


#: bf16 peaks from public spec sheets; HBM/ICI figures are the same
#: per-chip constants bench.py's MFU math uses.  The "cpu" entry models
#: the 8-virtual-device test mesh: one shared host, collectives as
#: memcpys, generous per-collective latency (thread rendezvous).
CHIPS = {
    "v6":  ChipSpec("v6",  918.0e12, 32e9, 1640e9, 180e9, 1e-6, 2e-6,
                    h2d_bw=64e9),
    "v5p": ChipSpec("v5p", 459.0e12, 95e9, 2765e9, 200e9, 1e-6, 2e-6,
                    h2d_bw=64e9),
    "v5e": ChipSpec("v5e", 197.0e12, 16e9,  819e9,  50e9, 1e-6, 2e-6,
                    h2d_bw=32e9),
    "v4":  ChipSpec("v4",  275.0e12, 32e9, 1228e9, 100e9, 1e-6, 2e-6,
                    h2d_bw=32e9),
    "v3":  ChipSpec("v3",  123.0e12, 32e9,  900e9,  70e9, 1e-6, 2e-6,
                    h2d_bw=16e9),
    "cpu": ChipSpec("cpu",   40.0e9,  4e9,   20e9,   4e9, 30e-6, 150e-6,
                    efficiency=1.0, shared_host=True, h2d_bw=20e9),
}


#: ``device_kind`` substrings -> CHIPS key.  The v5e chip reports
#: itself as "TPU v5 lite".
_KIND_TO_CHIP = (("v6", "v6"), ("v5p", "v5p"), ("v5e", "v5e"),
                 ("v5 lite", "v5e"), ("v4", "v4"), ("v3", "v3"))


def chip_spec(devices=None) -> ChipSpec:
    """Match the running device kind to the constants table.  The CPU
    backend gets the "cpu" entry; an accelerator the table does not
    list is an error, never priced as some other chip."""
    devices = list(devices) if devices is not None else jax.devices()
    kind = (getattr(devices[0], "device_kind", "") or
            devices[0].platform or "").lower()
    if "cpu" in kind or devices[0].platform == "cpu":
        return CHIPS["cpu"]
    for needle, key in _KIND_TO_CHIP:
        if needle in kind:
            return CHIPS[key]
    raise ValueError(
        f"chip_spec: no constants for device_kind "
        f"{devices[0].device_kind!r} — add it to auto.CHIPS")


# ---------------------------------------------------------------------------
# Fleets — mixed chip types / speed-scaled stragglers (docs/cluster.md)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Fleet:
    """Per-device chip specs for a (possibly mixed) device fleet, in
    planner device order.  A homogeneous fleet prices exactly like the
    single-``ChipSpec`` path; a heterogeneous one switches the planner
    to the slowest-member roofline bound with per-device batch shares
    (see :func:`predict_time_fleet`)."""

    specs: Tuple[ChipSpec, ...]

    def __post_init__(self):
        if not self.specs:
            raise ValueError("a Fleet needs at least one device")

    @property
    def n_devices(self) -> int:
        return len(self.specs)

    @property
    def heterogeneous(self) -> bool:
        return len({s.name for s in self.specs}) > 1

    def slowest(self) -> ChipSpec:
        return min(self.specs, key=lambda s: s.sustained_flops())

    def name(self) -> str:
        """Canonical ``"v5e:4+v4:4"`` rendering (consecutive runs)."""
        parts, i = [], 0
        while i < len(self.specs):
            j = i
            while j < len(self.specs) and \
                    self.specs[j].name == self.specs[i].name:
                j += 1
            parts.append(f"{self.specs[i].name}:{j - i}")
            i = j
        return "+".join(parts)


def parse_fleet(text: str) -> Fleet:
    """Parse the fleet syntax: ``+``-joined members, each
    ``<chip>[*<scale>][:<count>]``.

    ``"v5e:4+v4:4"`` is four v5e chips plus four v4; ``"cpu*0.5:2"`` is
    two CPU virtual devices running at half speed (the straggler
    stand-in the mixed-fleet tier-1 tests use — a declared slowdown the
    cost model must rank correctly against the measured mesh).
    """
    specs = []
    for member in str(text).split("+"):
        member = member.strip()
        if not member:
            raise ValueError(f"empty fleet member in {text!r}")
        count = 1
        if ":" in member:
            member, _, c = member.rpartition(":")
            count = int(c)
        scale = 1.0
        if "*" in member:
            member, _, s = member.partition("*")
            scale = float(s)
        chip = member.strip()
        if chip not in CHIPS:
            raise ValueError(
                f"unknown chip {chip!r} in fleet {text!r} — known: "
                f"{sorted(CHIPS)}")
        if count < 1:
            raise ValueError(f"fleet member count must be >= 1: {text!r}")
        specs.extend([CHIPS[chip].scaled(scale)] * count)
    return Fleet(specs=tuple(specs))


def _fleet_of(fleet) -> Optional[Fleet]:
    """Normalize the ``fleet=`` argument: None, a :class:`Fleet`, the
    string syntax, or a sequence of :class:`ChipSpec`."""
    if fleet is None:
        return None
    if isinstance(fleet, Fleet):
        return fleet
    if isinstance(fleet, str):
        return parse_fleet(fleet)
    return Fleet(specs=tuple(fleet))


def apportion_shares(weights, total: int) -> Tuple[int, ...]:
    """Largest-remainder apportionment of ``total`` integer units
    proportional to ``weights`` — the per-device batch-share rule.  The
    shares sum to ``total`` EXACTLY (the planner never invents or drops
    examples); ties break toward the earlier device for determinism."""
    n = len(weights)
    wsum = float(sum(weights))
    if wsum <= 0:
        weights, wsum = [1.0] * n, float(n)
    quotas = [w / wsum * total for w in weights]
    shares = [int(q) for q in quotas]
    rest = total - sum(shares)
    by_frac = sorted(range(n), key=lambda i: (shares[i] - quotas[i], i))
    for i in by_frac[:rest]:
        shares[i] += 1
    return tuple(shares)


# ---------------------------------------------------------------------------
# Serve phase split — disaggregated prefill/decode placement
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServePhaseSplit:
    """Device assignment for a disaggregated serving deployment
    (:class:`apex_tpu.serve.DisaggregatedEngine`): ``prefill`` /
    ``decode`` are index tuples into the fleet's device order.  On a
    single device the phases colocate (``colocated=True``, both tuples
    ``(0,)``) — that is the unified engine, not a degenerate split."""

    prefill: Tuple[int, ...]
    decode: Tuple[int, ...]
    colocated: bool
    reason: str

    def name(self) -> str:
        if self.colocated:
            return "colocated"
        return f"prefill:{len(self.prefill)}+decode:{len(self.decode)}"


def plan_serve_phase_split(fleet=None, *, prefill_weight: float = 1.0,
                           decode_weight: float = 1.0) -> ServePhaseSplit:
    """Split a (possibly heterogeneous) fleet between the two serving
    phases.  Phase demands are opposite corners of the roofline:
    prefill is one wide compute-bound matmul over the prompt (ranked by
    ``sustained_flops``), decode re-reads the whole KV cache per token
    (ranked by ``hbm_bw``) — so in a mixed fleet the members with the
    most HBM bandwidth per unit compute go to decode and the
    biggest-MXU members to prefill.  Phase sizes come from
    :func:`apportion_shares` over the declared demand weights (tokens
    of prefill vs decode work per request, roughly prompt length vs
    ``max_new_tokens``), clamped so each phase keeps at least one
    device."""
    flt = _fleet_of(fleet)
    if flt is None:
        flt = Fleet(specs=(chip_spec(),))
    n = flt.n_devices
    if n == 1:
        return ServePhaseSplit(
            prefill=(0,), decode=(0,), colocated=True,
            reason="single device: phases colocated (unified engine)")
    n_pre, n_dec = apportion_shares(
        [float(prefill_weight), float(decode_weight)], n)
    n_pre = max(1, min(n - 1, n_pre))
    n_dec = n - n_pre
    bw_per_flop = [s.hbm_bw / max(s.sustained_flops(), 1.0)
                   for s in flt.specs]
    order = sorted(range(n), key=lambda i: (-bw_per_flop[i], i))
    decode_ids = tuple(sorted(order[:n_dec]))
    prefill_ids = tuple(sorted(order[n_dec:]))
    return ServePhaseSplit(
        prefill=prefill_ids, decode=decode_ids, colocated=False,
        reason=(f"{flt.name()}: decode→{n_dec} member(s) with the "
                f"highest HBM-BW per sustained FLOP, prefill→{n_pre} "
                f"compute-heaviest"))


# ---------------------------------------------------------------------------
# Model profile — XLA-measured FLOPs/activation footprint + capabilities
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelProfile:
    """Static-shape profile the cost model scales per plan.

    ``flops_per_example`` / ``act_bytes_per_example`` /
    ``hbm_bytes_per_example`` are linear-fit slopes over the batch dim
    measured from XLA's own cost analysis of the unsharded
    forward+backward at two probe batch sizes (``source="xla"``), or the
    6·N·tokens fallback when the model cannot lower unsharded
    (``source="analytic"``).  The ``*_fixed`` intercepts capture the
    batch-independent part (weights traffic, per-call scratch).
    """
    n_params: int
    param_shapes: tuple
    param_bytes_fp32: int
    half_itemsize: int                 # 0 when params stay fp32
    slots_per_param: int               # fp32 optimizer slot multiplicity
    batch_ref: int                     # global batch the plan prices for
    batch_bytes_per_example: float
    flops_per_example: float
    flops_fixed: float
    act_bytes_per_example: float
    act_bytes_fixed: float
    hbm_bytes_per_example: float
    hbm_bytes_fixed: float
    logits_bytes_per_example: float    # vocab-head working set (chunk lever)
    seq_len: Optional[int]
    vocab: Optional[int]
    hidden: Optional[int]
    layers: Optional[int]
    heads: Optional[int]
    tp_axis: Optional[str]             # model capability (build option)
    sp_axis: Optional[str]
    source: str = "xla"
    # -- planner-v3 capabilities (defaults keep old profiles valid) ----
    pp_axis: Optional[str] = None      # PipelinedStack mesh axis
    remat_capable: bool = False        # model built with remat=True
    moe_axis: Optional[str] = None     # switch-MoE routing axis
    n_experts: int = 0                 # experts per MoE block (E)
    moe_layers: int = 0                # routed blocks in the model
    moe_param_frac: float = 0.0        # fraction of params in experts
    moe_capacity_factor: float = 1.25


def _optimizer_slots(optimizer) -> int:
    from ..optimizers import FusedAdam, FusedLAMB, FusedNovoGrad, FusedSGD
    if isinstance(optimizer, (FusedAdam, FusedLAMB)):
        return 2
    if isinstance(optimizer, (FusedSGD, FusedNovoGrad)):
        return 1
    return 2        # unknown: price like Adam, the common case


def _batch_leaves(batch_el):
    return [a for a in jax.tree_util.tree_leaves(batch_el)
            if hasattr(a, "shape")]


def _global_batch_of(example_batch) -> int:
    leaves = _batch_leaves(example_batch[0])
    if not leaves or not leaves[0].shape:
        raise ValueError(
            "example_batch[0] (the model input) has no leading batch "
            "dimension — the planner needs the global batch size")
    return int(leaves[0].shape[0])


def _resize_batch(example_batch, b):
    """ShapeDtypeStruct copy of the batch with splittable elements'
    leading dim set to ``b`` (same broadcast rule as the fused step:
    elements whose every leaf shares the model input's batch dim
    split, anything else is carried whole)."""
    n0 = _global_batch_of(example_batch)

    def splittable(el):
        leaves = _batch_leaves(el)
        return bool(leaves) and all(
            len(a.shape) >= 1 and a.shape[0] == n0 for a in leaves)

    def resize(el, do):
        def leaf(a):
            shape = ((b,) + tuple(a.shape[1:])) if do else tuple(a.shape)
            return jax.ShapeDtypeStruct(shape, jnp.dtype(a.dtype))
        return jax.tree_util.tree_map(leaf, el)

    return tuple(resize(el, i == 0 or splittable(el))
                 for i, el in enumerate(example_batch))


def _introspect(model):
    blocks = getattr(model, "blocks", None)
    layers = len(blocks) if blocks is not None else None
    heads = None
    if blocks is not None and len(blocks):
        for attr in ("heads", "num_heads", "n_heads"):
            heads = getattr(blocks[0], attr, None)
            if heads is None:
                attn = getattr(blocks[0], "attn", None)
                heads = getattr(attn, "heads", None) if attn is not None \
                    else None
            if heads is not None:
                break
    # switch-MoE capability: routed blocks carry num_experts + moe_axis
    # and the stacked expert FFN weights (w1/b1/w2/b2, leading dim E)
    moe_axis, n_experts, moe_layers, expert_bytes = None, 0, 0, 0
    moe_cap = 1.25
    for blk in (blocks or []):
        e = getattr(blk, "num_experts", None)
        if e is None or getattr(blk, "moe_axis", None) is None:
            continue
        moe_axis = blk.moe_axis
        n_experts = int(e)
        moe_layers += 1
        moe_cap = float(getattr(blk, "capacity_factor", moe_cap))
        for attr in ("w1", "b1", "w2", "b2"):
            p = getattr(blk, attr, None)
            if p is not None and hasattr(p, "data"):
                expert_bytes += int(np.prod(p.data.shape)) * 4
    # pipeline capability: a PipelinedStack (stacked stage params sliced
    # over axis_name, microbatch axis = accumulation unit)
    pp_axis = (getattr(model, "axis_name", None)
               if getattr(model, "n_micro", None) is not None and
               getattr(model, "stage_fn", None) is not None else None)
    return dict(
        vocab=getattr(model, "vocab_size", None),
        hidden=getattr(model, "hidden", None),
        layers=layers, heads=heads,
        tp_axis=getattr(model, "tp_axis", None),
        sp_axis=getattr(model, "sp_axis", None),
        pp_axis=pp_axis,
        remat_capable=bool(getattr(model, "remat", False)
                           or getattr(model, "remat_stage", False)),
        moe_axis=moe_axis, n_experts=n_experts, moe_layers=moe_layers,
        moe_capacity_factor=moe_cap,
        _expert_bytes=expert_bytes)


def profile_model(model, optimizer, loss_fn: Callable, example_batch, *,
                  half_dtype=None, keep_batchnorm_fp32: bool = True,
                  rng_seed: int = 0) -> ModelProfile:
    """Measure the model's per-example FLOPs / activation / HBM-traffic
    slopes from XLA's own cost analysis of the unsharded fwd+bwd, at two
    probe batch sizes (pure lower+compile, nothing executes).

    A model built with ``tp_axis=``/``sp_axis=`` cannot trace unsharded
    (its forward psums over mesh axes), so it falls back to the analytic
    6·N FLOP estimate with ``source="analytic"``.
    """
    from ..training.step import _model_dtypes
    from ..nn.modules import Ctx

    params = [p for p in model.parameters() if p is not None]
    buffers = list(model.buffers())
    model_dtypes = _model_dtypes(model, params, half_dtype,
                                 keep_batchnorm_fp32)
    n_params = sum(int(np.prod(p.data.shape)) for p in params)
    param_bytes = n_params * 4
    half_itemsize = 0 if half_dtype is None else jnp.dtype(half_dtype).itemsize
    info = _introspect(model)
    info["moe_param_frac"] = (info.pop("_expert_bytes")
                              / max(param_bytes, 1))
    b_hi = _global_batch_of(example_batch)
    act_itemsize = half_itemsize or 4
    batch_bytes = sum(
        int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        for el in example_batch for a in _batch_leaves(el)) / max(b_hi, 1)

    leaves0 = _batch_leaves(example_batch[0])
    seq_len = (int(leaves0[0].shape[1])
               if leaves0 and len(leaves0[0].shape) >= 2
               and np.issubdtype(np.dtype(leaves0[0].dtype), np.integer)
               else info["layers"] and getattr(model, "max_positions", None))
    logits_bpe = (float(seq_len) * info["vocab"] * 4.0
                  if seq_len and info["vocab"] else 0.0)

    def fwd(vals, *batch):
        env = {id(p): v for p, v in zip(params, vals)}
        env.update({id(bf): jnp.asarray(bf.data) for bf in buffers})
        ctx = Ctx(env=env, stats_out={}, training=True,
                  key=jax.random.PRNGKey(rng_seed))
        x = batch[0]
        if half_dtype is not None:
            from ..amp.policy import _cast_tree
            x = _cast_tree(x, jnp.dtype(half_dtype))
        out = model.forward(ctx, x)
        loss = loss_fn(out, *batch[1:])
        if ctx.aux_losses:
            loss = loss + sum(ctx.aux_losses)
        return loss.astype(jnp.float32)

    vals_struct = [jax.ShapeDtypeStruct(tuple(p.data.shape), jnp.dtype(d))
                   for p, d in zip(params, model_dtypes)]
    b_lo = max(1, b_hi // 2)
    if b_lo == b_hi:
        b_hi = b_lo + 1

    def probe(b):
        batch = _resize_batch(example_batch, b)
        lowered = jax.jit(jax.value_and_grad(fwd)).lower(
            vals_struct, *batch)
        ca = lowered.cost_analysis()
        ma = lowered.compile().memory_analysis()
        return (float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)),
                float(ma.temp_size_in_bytes))

    common = dict(
        n_params=n_params,
        param_shapes=tuple(tuple(p.data.shape) for p in params),
        param_bytes_fp32=param_bytes,
        half_itemsize=half_itemsize,
        slots_per_param=_optimizer_slots(optimizer),
        batch_ref=_global_batch_of(example_batch),
        batch_bytes_per_example=batch_bytes,
        logits_bytes_per_example=logits_bpe,
        seq_len=seq_len, **info)

    # models whose forward binds mesh axes (tp/sp psums, MoE routing's
    # axis_index, the pipeline stack's stage slicing) cannot lower
    # unsharded — fall back to the analytic 6·N estimate
    if (info["tp_axis"] is not None or info["sp_axis"] is not None
            or info["moe_axis"] is not None
            or info["pp_axis"] is not None):
        tokens = float(seq_len or 1)
        flops_pe = 6.0 * n_params * tokens
        return ModelProfile(
            flops_per_example=flops_pe, flops_fixed=0.0,
            act_bytes_per_example=12.0 * act_itemsize * (
                (info["layers"] or 1) * (info["hidden"] or n_params ** 0.5)
                * tokens) + logits_bpe,
            act_bytes_fixed=0.0,
            hbm_bytes_per_example=flops_pe / 50.0, hbm_bytes_fixed=0.0,
            source="analytic", **common)

    f_lo, h_lo, a_lo = probe(b_lo)
    f_hi, h_hi, a_hi = probe(b_hi)
    db = b_hi - b_lo

    def fit(lo, hi):
        slope = max((hi - lo) / db, 0.0)
        return slope, max(lo - slope * b_lo, 0.0)

    f_s, f_0 = fit(f_lo, f_hi)
    h_s, h_0 = fit(h_lo, h_hi)
    a_s, a_0 = fit(a_lo, a_hi)
    return ModelProfile(
        flops_per_example=f_s, flops_fixed=f_0,
        act_bytes_per_example=a_s, act_bytes_fixed=a_0,
        hbm_bytes_per_example=h_s, hbm_bytes_fixed=h_0,
        source="xla", **common)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------

#: remat policy → (keep_frac, recompute_frac).  ``keep_frac`` scales the
#: HBM model's activation term (what survives to the backward);
#: ``recompute_frac`` is the extra forward work as a fraction of the
#: step's total FLOPs, fed back into the roofline.  "selective" is the
#: checkpoint-every-other-boundary policy; "full" re-runs essentially
#: the whole forward from layer boundaries (the 1F1B stack's policy).
REMAT_POLICIES = {
    "none":      (1.0, 0.0),
    "selective": (0.5, 1.0 / 6.0),
    "full":      (0.15, 1.0 / 3.0),
}

#: deterministic tie-break order for the remat axis (lighter first)
_REMAT_ORDER = {"none": 0, "selective": 1, "full": 2}

#: the (offload_opt, offload_act) rungs the joint enumeration crosses
#: with every mesh/remat point: nothing, full optimizer-state offload,
#: and optimizer state + half the activations
OFFLOAD_LADDER = ((0.0, 0.0), (1.0, 0.0), (1.0, 0.5))

#: fraction of the offload transfer that stays exposed even when the
#: executor's h2d overlap is on (the prologue/epilogue of each window
#: cannot hide under compute)
OFFLOAD_EXPOSED_OVERLAPPED = 0.25


@dataclasses.dataclass(frozen=True)
class Plan:
    """One point in the joint (dp × sp × tp × zero × accum × chunked ×
    pp × remat × offload × ep) space, with the cost model's predictions
    attached.  Hashable — the structural part (:meth:`key`) is embedded
    in step-program cache keys so compiled executables are per-plan
    observables."""
    dp: int = 1
    tp: int = 1
    sp: int = 1
    zero_stage: int = 0
    accum: int = 1
    chunked_loss: bool = False
    #: pipeline stages (devices along the pp axis) and microbatches per
    #: step — the pipeline's accumulation unit (pp plans keep accum=1)
    pp: int = 1
    micro: int = 1
    #: activation-checkpoint policy: a :data:`REMAT_POLICIES` key
    remat: str = "none"
    #: expert-parallel degree — rides the dp axis (ep == dp == E, one
    #: expert per device along the model's moe_axis)
    ep: int = 1
    #: host-offload fractions: optimizer state (masters + slots) and
    #: activations moved to host RAM, priced at the measured H2D rate
    offload_opt: float = 0.0
    offload_act: float = 0.0
    dp_axis: str = "data"
    tp_axis: Optional[str] = None
    sp_axis: Optional[str] = None
    pp_axis: Optional[str] = None
    #: heterogeneous pipelines: layers per stage (apportion_shares over
    #: member speeds) and the chip name hosting each stage.  Empty on a
    #: homogeneous pipeline (uniform layers/pp split).
    stage_layers: tuple = ()
    stage_members: tuple = ()
    n_devices: int = 1                   # devices the planner priced for
    predicted_ms: Optional[float] = None
    predicted_hbm: Optional[int] = None
    breakdown: tuple = ()                # ((name, value), ...) — hashable
    collectives: tuple = ()
    measured_ms: Optional[float] = None
    #: heterogeneous fleets only: per-device batch shares (ints summing
    #: EXACTLY to the global batch, device order) — the planner's
    #: replacement for the uniform global_batch/dp split.  Empty on a
    #: homogeneous fleet (uniform split applies).
    device_shares: tuple = ()

    def key(self):
        """The structural identity embedded in program cache keys.

        The first six positions are the historical (dp, tp, sp, zero,
        accum, chunked) tuple — a plan using none of the new axes keys
        exactly as it did before, so old checkpoints/manifests and the
        step cache stay valid.  Each non-default new axis appends one
        tagged STRING segment (``"pp4"``, ``"micro8"``,
        ``"remat=selective"``, ``"ep8"``, ``"offopt=1"``,
        ``"offact=0.5"``) that :func:`plan_from_key` parses back."""
        base = (self.dp, self.tp, self.sp, self.zero_stage, self.accum,
                self.chunked_loss)
        extra = []
        if self.pp != 1:
            extra.append(f"pp{self.pp}")
        if self.micro != 1:
            extra.append(f"micro{self.micro}")
        if self.remat != "none":
            extra.append(f"remat={self.remat}")
        if self.ep != 1:
            extra.append(f"ep{self.ep}")
        if self.offload_opt:
            extra.append(f"offopt={self.offload_opt:g}")
        if self.offload_act:
            extra.append(f"offact={self.offload_act:g}")
        return base + tuple(extra)

    @property
    def n_used(self) -> int:
        return self.dp * self.tp * self.sp * self.pp

    def name(self) -> str:
        parts = [f"dp{self.dp}"]
        if self.sp > 1:
            parts.append(f"sp{self.sp}")
        if self.tp > 1:
            parts.append(f"tp{self.tp}")
        if self.pp > 1:
            parts.append(f"pp{self.pp}")
            if self.micro > 1:
                parts.append(f"m{self.micro}")
        if self.ep > 1:
            parts.append(f"ep{self.ep}")
        if self.remat != "none":
            parts.append(f"remat[{self.remat}]")
        if self.offload_opt or self.offload_act:
            parts.append(f"off[opt{self.offload_opt:g}"
                         f"+act{self.offload_act:g}]")
        if self.zero_stage:
            parts.append(f"zero{self.zero_stage}")
        if self.accum > 1:
            parts.append(f"K{self.accum}")
        if self.chunked_loss:
            parts.append("chunked")
        return "·".join(parts)

    def step_kwargs(self, devices=None) -> dict:
        """The existing entry-point knobs this plan threads — the
        planner drives tested primitives, it adds no execution path.

        dp/ZeRO plans map to the GSPMD ``zero_sharding`` path; tp/sp/ep
        plans to the explicit-axis ``shard_map`` path (an ep plan's data
        axis IS the model's moe_axis); pp plans to the pipeline entry
        points — ``make_pipeline_train_step(schedule="1f1b")`` for
        ``remat="full"``, ``make_train_step(tp_axis=<pp axis>)`` (the
        GPipe stack wrap) otherwise."""
        kw = {}
        if self.pp > 1:
            if self.remat == "full":
                kw["schedule"] = "1f1b"      # make_pipeline_train_step
            else:
                kw["tp_axis"] = self.pp_axis or "pp"
            return kw
        if self.accum > 1:
            kw["accum_steps"] = self.accum
        if self.tp == 1 and self.sp == 1 and self.ep == 1:
            if self.dp > 1:
                kw.update(zero_sharding=True, zero_stage=self.zero_stage,
                          zero_axis=self.dp_axis)
                if devices is not None:
                    kw["zero_mesh"] = Mesh(
                        np.array(list(devices)[:self.dp]), (self.dp_axis,))
        else:
            axes = []
            if self.dp > 1:
                axes.append(self.dp_axis)
            if self.sp > 1:
                axes.append(self.sp_axis)
            if axes:
                kw["axis_name"] = axes[0] if len(axes) == 1 else tuple(axes)
            if self.tp > 1:
                kw["tp_axis"] = self.tp_axis
        return kw

    def _fmt_bytes(self, b):
        return f"{b / 2**30:.2f} GiB" if b >= 2**30 else \
            f"{b / 2**20:.1f} MiB"

    def describe(self) -> str:
        bd = dict(self.breakdown)
        mesh = f"mesh dp={self.dp} sp={self.sp} tp={self.tp}"
        if self.pp > 1:
            mesh += f" pp={self.pp}"
        if self.ep > 1:
            mesh += f" ep={self.ep}"
        lines = [
            f"Plan {self.name()}  ({mesh}, "
            f"{self.n_used} of {self.n_devices} devices, "
            f"ZeRO stage {self.zero_stage}, accum K={self.accum}, "
            f"chunked_loss={'on' if self.chunked_loss else 'off'})"]
        if self.predicted_ms is not None:
            lines.append(f"  predicted {self.predicted_ms:.3f} ms/step"
                         + (f" (measured {self.measured_ms:.3f})"
                            if self.measured_ms is not None else ""))
            lines.append(
                "  time: compute {:.3f} + hbm {:.3f} (roofline max) "
                "+ collectives {:.3f} + overhead {:.3f} ms".format(
                    bd.get("compute_ms", 0.0), bd.get("hbm_ms", 0.0),
                    bd.get("collective_ms", 0.0),
                    bd.get("overhead_ms", 0.0)))
        if self.pp > 1:
            sched = "1F1B" if self.remat == "full" else "GPipe"
            ticks = int(bd.get("pp_ticks",
                               self.micro + 2 * (self.pp - 1)))
            frac = bd.get("bubble_frac",
                          2.0 * (self.pp - 1) / max(ticks, 1))
            lines.append(
                f"  pipeline: {self.pp} stages × {self.micro} "
                f"microbatches ({sched} schedule), {ticks} ticks/step, "
                f"bubble fraction {frac:.1%}")
            if self.stage_layers:
                members = self.stage_members or ("?",) * len(
                    self.stage_layers)
                lines.append("  stage placement: " + "; ".join(
                    f"stage {i} → {m} ({l} layer"
                    + ("s" if l != 1 else "") + ")"
                    for i, (l, m) in enumerate(
                        zip(self.stage_layers, members))))
        if self.remat != "none":
            keep, rec = REMAT_POLICIES[self.remat]
            gf = bd.get("recompute_gflops", 0.0)
            lines.append(
                f"  remat[{self.remat}]: keep {keep:.0%} of activations"
                f", recompute {gf:.2f} GFLOP/step "
                f"(+{rec:.0%} of step FLOPs re-run in the backward)")
        if self.offload_opt or self.offload_act:
            traffic = bd.get("offload_bytes", 0)
            lines.append(
                f"  offload: optimizer state {self.offload_opt:.0%} "
                f"(host {self._fmt_bytes(bd.get('host_opt_bytes', 0))}), "
                f"activations {self.offload_act:.0%} "
                f"(host {self._fmt_bytes(bd.get('host_act_bytes', 0))}) "
                f"— offload bytes {self._fmt_bytes(traffic)}/step over "
                f"H2D/D2H, {bd.get('offload_ms', 0.0):.3f} ms exposed")
        if self.ep > 1:
            lines.append(
                f"  expert parallel: ep={self.ep} (one expert per "
                f"device along {self.dp_axis!r}; dispatch/combine "
                f"all-to-all priced per routed block)")
        if self.device_shares:
            lines.append(
                "  device batch shares: ["
                + ", ".join(str(s) for s in self.device_shares)
                + "] (heterogeneous fleet — slowest-member bound; "
                "shares sum to the global batch)")
        if self.predicted_hbm is not None:
            mem = " + ".join(
                f"{k[4:]} {self._fmt_bytes(v)}"
                for k, v in self.breakdown if k.startswith("mem_"))
            unit = ("per-stage HBM (largest stage)" if self.pp > 1
                    else "predicted HBM")
            lines.append(f"  {unit} "
                         f"{self._fmt_bytes(self.predicted_hbm)}"
                         f"/device = {mem}")
        if self.collectives:
            lines.append("  collectives: " + "; ".join(self.collectives))
        else:
            lines.append("  collectives: none (single-device program)")
        kw = self.step_kwargs()
        if kw:
            lines.append("  knobs: " + ", ".join(
                f"{k}={v!r}" for k, v in kw.items()))
        if self.chunked_loss:
            lines.append(
                "  note: priced with the chunked LM head+loss "
                "(contrib.chunked_lm_loss) — the plan does not swap your "
                "loss_fn; see docs/auto_parallel.md")
        return "\n".join(lines)


def static_plan_key(plan):
    """Hashable normalization used by the step-program cache keys (re-
    exported by runtime.step_cache); None passes through for unplanned
    steps."""
    return None if plan is None else plan.key()


#: tagged plan-key segments: prefix → (Plan field, parser).  The
#: ordering here is the canonical emission order of :meth:`Plan.key`.
_KEY_SEGMENTS = (
    ("pp", "pp", int),
    ("micro", "micro", int),
    ("remat=", "remat", str),
    ("ep", "ep", int),
    ("offopt=", "offload_opt", float),
    ("offact=", "offload_act", float),
)


def plan_from_key(key, n_devices: int = 1) -> Plan:
    """Rebuild a structural :class:`Plan` from a saved manifest key —
    the inverse of :meth:`Plan.key` for the structural fields (cost-model
    predictions are not identity and come back unset).  The elastic
    restore path uses this to describe the plan a schema-2 checkpoint
    was saved under (``manifest["plan"]["key"]``).

    Unknown segments are an ERROR, not silently dropped: a manifest
    written by a newer planner names an axis this build cannot honor,
    and guessing would restore under the wrong plan."""
    key = tuple(key)
    if len(key) < 6:
        raise ValueError(
            f"plan key {key!r} is malformed: the first six segments "
            f"must be (dp, tp, sp, zero_stage, accum, chunked_loss)")
    dp, tp, sp, zero_stage, accum, chunked_loss = key[:6]
    kw = {}
    known = [p for p, _, _ in _KEY_SEGMENTS]
    for seg in key[6:]:
        if not isinstance(seg, str):
            raise ValueError(
                f"unknown plan-key segment {seg!r}: extended segments "
                f"are tagged strings with one of the prefixes {known}")
        for prefix, field, parse in _KEY_SEGMENTS:
            if seg.startswith(prefix):
                if field in kw:
                    raise ValueError(
                        f"plan key {key!r} repeats the {field!r} "
                        f"segment ({seg!r})")
                try:
                    kw[field] = parse(seg[len(prefix):])
                except ValueError:
                    raise ValueError(
                        f"plan-key segment {seg!r}: the {field!r} "
                        f"value {seg[len(prefix):]!r} does not parse "
                        f"as {parse.__name__}")
                break
        else:
            raise ValueError(
                f"unknown plan-key segment {seg!r}: this planner "
                f"recognizes no such field (known segment prefixes: "
                f"{known})")
    if kw.get("remat", "none") not in REMAT_POLICIES:
        raise ValueError(
            f"plan-key segment remat={kw['remat']!r}: unknown remat "
            f"policy (known: {sorted(REMAT_POLICIES)})")
    return Plan(dp=int(dp), tp=int(tp), sp=int(sp),
                zero_stage=int(zero_stage), accum=int(accum),
                chunked_loss=bool(chunked_loss), n_devices=int(n_devices),
                **kw)


# ---------------------------------------------------------------------------
# Cost model: memory feasibility + roofline step time
# ---------------------------------------------------------------------------

#: chunked LM loss default chunk count: the working-set divisor the
#: memory lever is priced at (contrib's default chunking)
CHUNKS = 8

#: fraction of HBM the planner refuses to plan into (XLA scratch,
#: fragmentation, the runtime's own buffers)
HBM_RESERVE = 0.08


def _zero_shard_bytes(prof: ModelProfile, itemsize: int, n: int) -> int:
    """Exact per-tensor ZeRO sharding: dim-0-divisible tensors shard n
    ways, the rest stay replicated (zero.py's `_leaf_sharding` rule)."""
    total = 0
    for shape in prof.param_shapes:
        b = int(np.prod(shape)) * itemsize
        if n > 1 and shape and shape[0] >= n and shape[0] % n == 0:
            b //= n
        total += b
    return total


def _param_scale(plan: Plan, prof: ModelProfile) -> float:
    """Fraction of the parameter state one device holds under the
    plan's pipeline-stage slice and expert sharding (before ZeRO, which
    :func:`_zero_shard_bytes` handles per-tensor)."""
    scale = 1.0
    if plan.pp > 1:
        if plan.stage_layers:
            scale *= max(plan.stage_layers) / max(sum(plan.stage_layers),
                                                  1)
        else:
            scale *= 1.0 / plan.pp
    if plan.ep > 1 and prof.moe_param_frac:
        # expert weights shard one-per-device; the dense remainder is
        # replicated along the (ep == dp) axis
        scale *= ((1.0 - prof.moe_param_frac)
                  + prof.moe_param_frac / plan.ep)
    return scale


def _pp_boundary_bytes(plan: Plan, prof: ModelProfile,
                       micro_b: float) -> float:
    """One microbatch's stage-boundary activation (the tensor ppermute
    hops stage-to-stage): hidden × seq when the profile knows the
    geometry, else one layer's share of the activation slope."""
    act_itemsize = prof.half_itemsize or 4
    if prof.hidden and prof.seq_len:
        return float(prof.hidden) * prof.seq_len * micro_b * act_itemsize
    return (prof.act_bytes_per_example * micro_b
            / max(prof.layers or plan.pp, 1))


def predict_memory(plan: Plan, prof: ModelProfile, spec: ChipSpec,
                   global_batch: int):
    """Per-device steady-state training footprint: returns
    ``(total_bytes, breakdown)`` with one entry per component.

    v3 axes: pipeline plans hold one STAGE's parameter state plus the
    schedule's in-flight microbatch activations (GPipe keeps every
    tick's residuals; 1F1B — ``remat="full"`` — keeps a ring of
    boundary inputs and recomputes internals); ``remat`` scales the
    surviving activation term by its keep-fraction; ``offload`` moves
    optimizer state / activations to host RAM (reported as ``host_*``
    breakdown entries, not HBM); ``ep`` shards the expert slice of the
    parameter state one-per-device."""
    pscale = _param_scale(plan, prof)
    keep_frac, _rec = REMAT_POLICIES[plan.remat]
    shard_n = plan.dp if plan.zero_stage >= 1 else 1
    masters_full = _zero_shard_bytes(prof, 4, shard_n) * pscale
    opt_full = (1 + prof.slots_per_param) * masters_full
    masters = masters_full * (1.0 - plan.offload_opt)
    slots = prof.slots_per_param * masters_full * (1.0 - plan.offload_opt)
    host_opt = opt_full * plan.offload_opt
    half = 0
    if prof.half_itemsize:
        half = _zero_shard_bytes(
            prof, prof.half_itemsize,
            plan.dp if plan.zero_stage == 3 else 1) * pscale
    # gradient carry/working set, per path: the K>1 scan holds a full
    # replicated fp32 accumulator; a K=1 ZeRO program's gradients land
    # reduce-scattered (per-device 1/dp); a stage-0 all-reduce holds
    # grad + collective double buffer; single-device holds one grad set.
    # Gradients are NEVER offloaded: they are produced and consumed
    # inside one step, so a host round-trip would serialize the update.
    if plan.accum > 1:
        # window accumulator + the per-microbatch gradient it adds
        grads = 2 * prof.param_bytes_fp32 * pscale
    elif plan.zero_stage >= 1 and plan.dp > 1:
        # reduce-scattered shards, double-buffered through the collective
        grads = 2 * _zero_shard_bytes(prof, 4, plan.dp) * pscale
    elif plan.dp > 1 or plan.pp > 1:
        # full grads + the collective double buffer (dp all-reduce, or
        # the pipeline's stage-grad assembly psum)
        grads = 2 * prof.param_bytes_fp32 * pscale
    else:
        grads = prof.param_bytes_fp32 * pscale
    micro_b = global_batch / (plan.dp * plan.accum * plan.micro)
    tp_act = (1.0 + 1.0 / plan.tp) / 2.0   # sharded FFN/heads, full residual
    acts = (prof.act_bytes_per_example * micro_b / plan.sp * tp_act
            + prof.act_bytes_fixed)
    if plan.chunked_loss and prof.logits_bytes_per_example:
        acts -= (prof.logits_bytes_per_example * micro_b / plan.sp
                 * (1.0 - 1.0 / CHUNKS))
        acts = max(acts, 0.0)
    if plan.pp > 1:
        stage_frac = (max(plan.stage_layers) / max(sum(plan.stage_layers),
                                                   1)
                      if plan.stage_layers else 1.0 / plan.pp)
        internals = acts * stage_frac * keep_frac
        boundary = _pp_boundary_bytes(plan, prof, micro_b)
        if plan.remat == "full":
            # 1F1B: one microbatch's internals live (recomputed in the
            # backward), boundary inputs in the schedule's ring buffer
            from .pipeline import ring_slots
            acts = internals + boundary * ring_slots(plan.pp, plan.micro)
        else:
            # GPipe scan: the transpose keeps every tick's residuals
            inflight = plan.micro + plan.pp - 1
            acts = (internals + boundary) * inflight
    else:
        acts *= keep_frac
    host_act = acts * plan.offload_act
    acts -= host_act
    batch = prof.batch_bytes_per_example * global_batch / plan.dp / plan.sp
    bd = [("mem_masters", int(masters)), ("mem_slots", int(slots)),
          ("mem_half", int(half)), ("mem_grads", int(grads)),
          ("mem_acts", int(acts)), ("mem_batch", int(batch))]
    if host_opt or host_act:
        # host_* entries are NOT "mem_"-prefixed: they live in host RAM,
        # outside the per-device HBM sum describe() reports
        bd.append(("host_opt_bytes", int(host_opt)))
        bd.append(("host_act_bytes", int(host_act)))
    return (int(masters + slots + half + grads + acts + batch), bd)


def _ring_all_reduce_s(bytes_, n, spec):
    if n <= 1 or bytes_ <= 0:
        return 0.0
    return 2 * (n - 1) / n * bytes_ / spec.ici_bw \
        + 2 * (n - 1) * spec.ici_latency_s


def _ring_half_s(bytes_, n, spec):
    """One reduce-scatter OR all-gather pass."""
    if n <= 1 or bytes_ <= 0:
        return 0.0
    return (n - 1) / n * bytes_ / spec.ici_bw + (n - 1) * spec.ici_latency_s


def _dp_collective_terms(plan: Plan, prof: ModelProfile, spec: ChipSpec,
                         w_itemsize: int, param_scale: float = 1.0):
    """The dp-axis collective terms (stage-0 grad all-reduce, or the
    ZeRO reduce-scatter / param all-gather pair, plus the stage-3
    per-microbatch gather with the executor's prefetch overlap).
    Shared between :func:`predict_time` and :func:`predict_time_fleet`
    — the fleet path hands in a slowest-link spec so every collective
    is priced at the weakest interconnect in the ring.  ``param_scale``
    shrinks the exchanged gradient/parameter bytes for plans whose
    per-device parameter state is a slice (pipeline stage, expert
    shard)."""
    coll_s, colls = 0.0, []
    gbytes = prof.param_bytes_fp32 * param_scale
    if plan.dp > 1:
        if plan.zero_stage == 0:
            coll_s += _ring_all_reduce_s(gbytes, plan.dp, spec)
            colls.append(f"all-reduce fp32 grads ({_mib(gbytes)}) over "
                         f"{plan.dp_axis}({plan.dp}) at the window boundary")
        else:
            coll_s += _ring_half_s(gbytes, plan.dp, spec)
            colls.append(f"reduce-scatter fp32 grads ({_mib(gbytes)}) into "
                         f"master shards over {plan.dp_axis}({plan.dp})")
            ag = prof.n_params * w_itemsize * param_scale
            coll_s += _ring_half_s(ag, plan.dp, spec)
            colls.append(f"all-gather updated params ({_mib(ag)}) over "
                         f"{plan.dp_axis}({plan.dp})")
        if plan.zero_stage == 3:
            from ..runtime import executor as _executor
            ag1 = prof.n_params * w_itemsize * param_scale
            ag3 = plan.accum * ag1
            if plan.accum > 1 and _executor.overlap_enabled("gather"):
                # executor gather prefetch: the scanned window issues
                # microbatch i+1's param gather under microbatch i's
                # compute, so only the prologue gather stays exposed
                coll_s += _ring_half_s(ag1, plan.dp, spec)
                colls.append(
                    f"per-microbatch param all-gather (stage 3, "
                    f"K×{_mib(ag1)} = {_mib(ag3)}/step; prefetch "
                    f"overlaps all but the prologue gather)")
            else:
                coll_s += plan.accum * _ring_half_s(ag1, plan.dp, spec)
                colls.append(f"per-microbatch param all-gather (stage 3, "
                             f"K×{_mib(ag1)} = "
                             f"{_mib(ag3)}/step)")
    return coll_s, colls


def _moe_a2a_terms(plan: Plan, prof: ModelProfile, spec: ChipSpec,
                   micro_b: float, micro_n: int):
    """The expert-parallel dispatch/combine all-to-all: per routed block
    the forward sends each token's hidden vector to its expert's device
    and gathers the result back (2 exchanges), and the backward mirrors
    both (4 total), each moving the (ep-1)/ep off-device fraction of the
    capacity-scaled token buffer."""
    act_itemsize = prof.half_itemsize or 4
    tokens = micro_b * float(prof.seq_len or 1)
    xfer = (tokens * float(prof.hidden or 1) * act_itemsize
            * prof.moe_capacity_factor)
    per_a2a = ((plan.ep - 1) / plan.ep * xfer / spec.ici_bw
               + (plan.ep - 1) * spec.ici_latency_s)
    n_a2a = 4 * prof.moe_layers * micro_n
    coll_s = n_a2a * per_a2a
    desc = (f"MoE dispatch/combine all-to-all ({_mib(xfer)}/exchange × "
            f"{n_a2a}: 4 per routed block × {prof.moe_layers} blocks × "
            f"{micro_n} microbatches) over {plan.dp_axis}({plan.ep})")
    return coll_s, desc


def predict_time(plan: Plan, prof: ModelProfile, spec: ChipSpec,
                 global_batch: int):
    """Roofline step time: ``max(compute, HBM) + collectives + overhead``.
    Returns ``(ms, breakdown, collectives)``.

    v3 axes: ``remat`` adds its recompute FLOPs (and the matching HBM
    re-reads) to the roofline; ``pp`` applies the warmup/drain bubble
    multiplier over the microbatch schedule plus the stage-boundary
    ppermutes and stage-grad assembly; ``offload`` adds the exposed
    fraction of the host round-trip priced at the executor's measured
    H2D bandwidth (``spec.h2d_bw`` prior); ``ep`` adds the MoE
    dispatch/combine all-to-all per routed block."""
    n_used = plan.n_used
    micro_n = plan.accum * plan.micro      # microbatches per step
    micro_b = global_batch / (plan.dp * micro_n)
    act_itemsize = prof.half_itemsize or 4
    w_itemsize = prof.half_itemsize or 4
    keep_frac, rec_frac = REMAT_POLICIES[plan.remat]
    pscale = _param_scale(plan, prof)

    base_flops = (prof.flops_per_example * global_batch / n_used
                  + micro_n * prof.flops_fixed)
    flops = base_flops * (1.0 + rec_frac)
    # virtual devices split one host: per-plan sustained rate is the
    # host's, not n_used × the host's
    sustained = spec.sustained_flops() / (n_used if spec.shared_host else 1)
    compute_s = flops / sustained

    weight_traffic = (micro_n * prof.n_params * w_itemsize * pscale
                      / plan.tp)
    if plan.zero_stage == 3:
        weight_traffic /= plan.dp
    hbm_bytes = ((prof.hbm_bytes_per_example * global_batch / n_used)
                 * (1.0 + rec_frac)
                 + micro_n * prof.hbm_bytes_fixed + weight_traffic)
    if plan.chunked_loss and prof.logits_bytes_per_example:
        hbm_bytes -= (prof.logits_bytes_per_example * global_batch / n_used
                      * (1.0 - 1.0 / CHUNKS))
    hbm_bw = spec.hbm_bw / (n_used if spec.shared_host else 1)
    hbm_s = max(hbm_bytes, 0.0) / hbm_bw

    extra_bd = []
    if plan.remat != "none":
        extra_bd.append(("recompute_gflops",
                         base_flops * rec_frac / 1e9))
    if plan.pp > 1:
        # warmup/drain bubble: (pp-1) fill ticks before the first and
        # after the last full microbatch — both schedules pay it
        bubble_mult = (plan.micro + plan.pp - 1) / plan.micro
        compute_s *= bubble_mult
        hbm_s *= bubble_mult
        ticks = plan.micro + 2 * (plan.pp - 1)
        extra_bd.append(("pp_ticks", float(ticks)))
        extra_bd.append(("bubble_frac",
                         (plan.pp - 1) / (plan.micro + plan.pp - 1)))

    coll_s, colls = _dp_collective_terms(plan, prof, spec, w_itemsize,
                                         param_scale=pscale)
    if plan.pp > 1:
        boundary = _pp_boundary_bytes(plan, prof, micro_b)
        hop_s = boundary / spec.ici_bw + spec.ici_latency_s
        # one fwd send + one bwd send per microbatch per stage boundary
        coll_s += 2 * plan.micro * hop_s
        colls.append(f"stage-boundary ppermute ({_mib(boundary)}/hop, "
                     f"2×{plan.micro} hops/step) over "
                     f"{plan.pp_axis or 'pp'}({plan.pp})")
        gb_stage = prof.param_bytes_fp32 * pscale
        coll_s += _ring_all_reduce_s(prof.param_bytes_fp32, plan.pp, spec)
        colls.append(f"stage-grad assembly psum ({_mib(gb_stage)} live "
                     f"of {_mib(prof.param_bytes_fp32)} stacked) over "
                     f"{plan.pp_axis or 'pp'}({plan.pp})")
    if plan.ep > 1 and prof.moe_layers:
        a2a_s, a2a_desc = _moe_a2a_terms(plan, prof, spec, micro_b,
                                         micro_n)
        coll_s += a2a_s
        colls.append(a2a_desc)
    gbytes = prof.param_bytes_fp32
    if plan.tp > 1:
        if prof.layers and prof.hidden and prof.seq_len:
            per_micro = (4.0 * prof.layers * micro_b * prof.seq_len
                         / plan.sp * prof.hidden * act_itemsize)
        else:
            per_micro = 0.5 * prof.act_bytes_per_example * micro_b
        tp_bytes = plan.accum * per_micro
        coll_s += plan.accum * _ring_all_reduce_s(per_micro, plan.tp, spec)
        colls.append(f"activation all-reduce (row-parallel psum, "
                     f"{_mib(tp_bytes)}/step) over "
                     f"{plan.tp_axis or 'tp'}({plan.tp})")
        shard_grads = 0.66 * gbytes     # head/FFN block fraction
        coll_s += _ring_all_reduce_s(shard_grads, plan.tp, spec)
        colls.append(f"block-sparse grad assembly psum "
                     f"({_mib(shard_grads)}) over "
                     f"{plan.tp_axis or 'tp'}({plan.tp})")
    if plan.sp > 1:
        if prof.layers and prof.hidden and prof.seq_len:
            kv = (2.0 * prof.layers * micro_b * prof.seq_len
                  * prof.hidden * act_itemsize)
        else:
            kv = 0.3 * prof.act_bytes_per_example * micro_b
        coll_s += plan.accum * _ring_all_reduce_s(kv, plan.sp, spec)
        colls.append(f"ring ppermute of K/V blocks ({_mib(kv)}/microbatch) "
                     f"over {plan.sp_axis or 'sp'}({plan.sp})")
        coll_s += _ring_all_reduce_s(gbytes, plan.sp, spec)
        colls.append(f"all-reduce fp32 grads ({_mib(gbytes)}) over "
                     f"{plan.sp_axis or 'sp'}({plan.sp})")

    offload_s = 0.0
    if plan.offload_opt or plan.offload_act:
        from ..runtime import executor as _executor
        _, mem_bd = predict_memory(plan, prof, spec, global_batch)
        md = dict(mem_bd)
        # optimizer state rides host→device and back once per step;
        # activations go device→host in the forward, back in the
        # backward — 2× each component's resident host bytes
        host_traffic = 2 * (md.get("host_opt_bytes", 0)
                            + md.get("host_act_bytes", 0))
        h2d_bw = _executor.measured_h2d_bw() or spec.h2d_bw
        transfer_s = host_traffic / h2d_bw
        exposed = (OFFLOAD_EXPOSED_OVERLAPPED
                   if _executor.overlap_enabled("h2d") else 1.0)
        offload_s = transfer_s * exposed
        extra_bd.append(("offload_bytes", float(host_traffic)))
        extra_bd.append(("offload_ms", offload_s * 1e3))

    overhead_s = micro_n * spec.overhead_s
    total_s = max(compute_s, hbm_s) + coll_s + overhead_s + offload_s
    bd = [("compute_ms", compute_s * 1e3), ("hbm_ms", hbm_s * 1e3),
          ("collective_ms", coll_s * 1e3),
          ("overhead_ms", overhead_s * 1e3)] + extra_bd
    return total_s * 1e3, bd, colls


def predict_time_fleet(plan: Plan, prof: ModelProfile, fleet: Fleet,
                       global_batch: int, shares=None):
    """Slowest-member roofline for a heterogeneous fleet (AMP
    arXiv:2210.07297, Poplar arXiv:2408.12596): every member computes
    its batch SHARE, the step completes when the slowest member does,
    and collectives run at the weakest link in the ring.

    ``shares`` defaults to :func:`apportion_shares` proportional to each
    member's sustained rate; pass an explicit tuple (e.g. a uniform
    split) to price an alternative assignment — the mixed-fleet tier-1
    test prices both and pins that their predicted order matches the
    measured order on the CPU mesh.

    Returns ``(ms, breakdown, collectives, shares)``.  Fleet plans are
    dp-only (``_structural_reject`` enforces it), so only the dp
    collective terms appear.
    """
    n_used = plan.n_used
    specs = fleet.specs[:n_used]
    if len(specs) < n_used:
        raise ValueError(f"plan {plan.name()} needs {n_used} devices, "
                         f"fleet has {fleet.n_devices}")
    if plan.pp > 1:
        return _predict_time_fleet_pp(plan, prof, fleet, global_batch)
    if shares is None:
        shares = apportion_shares(
            [s.sustained_flops() for s in specs], global_batch)
    shares = tuple(int(s) for s in shares)
    if len(shares) != n_used or sum(shares) != global_batch:
        raise ValueError(
            f"device shares {shares} must have {n_used} entries summing "
            f"to the global batch {global_batch}")
    w_itemsize = prof.half_itemsize or 4

    # each member's roofline at its share; the step is bound by the
    # slowest member (max over members), not the mean
    bound_s, bound_i, bound_compute, bound_hbm = 0.0, 0, 0.0, 0.0
    for i, (spec, share) in enumerate(zip(specs, shares)):
        div = n_used if spec.shared_host else 1
        flops = (prof.flops_per_example * share
                 + plan.accum * prof.flops_fixed)
        compute_s = flops / (spec.sustained_flops() / div)
        weight_traffic = plan.accum * prof.n_params * w_itemsize
        if plan.zero_stage == 3:
            weight_traffic /= plan.dp
        hbm_bytes = (prof.hbm_bytes_per_example * share
                     + plan.accum * prof.hbm_bytes_fixed + weight_traffic)
        if plan.chunked_loss and prof.logits_bytes_per_example:
            hbm_bytes -= (prof.logits_bytes_per_example * share
                          * (1.0 - 1.0 / CHUNKS))
        hbm_s = max(hbm_bytes, 0.0) / (spec.hbm_bw / div)
        member_s = max(compute_s, hbm_s)
        if member_s > bound_s:
            bound_s, bound_i = member_s, i
            bound_compute, bound_hbm = compute_s, hbm_s

    # collectives at the slowest link: min bandwidth, max latency
    link = dataclasses.replace(
        fleet.slowest(),
        ici_bw=min(s.ici_bw for s in specs),
        ici_latency_s=max(s.ici_latency_s for s in specs))
    coll_s, colls = _dp_collective_terms(plan, prof, link, w_itemsize)
    if fleet.heterogeneous and coll_s > 0:
        colls.append(f"(all collectives priced at the slowest link: "
                     f"{link.ici_bw / 1e9:.1f} GB/s, "
                     f"{link.ici_latency_s * 1e6:.0f} us/hop)")

    overhead_s = plan.accum * max(s.overhead_s for s in specs)
    total_s = bound_s + coll_s + overhead_s
    bd = [("compute_ms", bound_compute * 1e3), ("hbm_ms", bound_hbm * 1e3),
          ("collective_ms", coll_s * 1e3),
          ("overhead_ms", overhead_s * 1e3),
          ("bound_member", float(bound_i))]
    return total_s * 1e3, bd, colls, shares


def _predict_time_fleet_pp(plan: Plan, prof: ModelProfile, fleet: Fleet,
                           global_batch: int):
    """Heterogeneous pipeline pricing: stage ``i`` lives on fleet member
    ``i`` with :attr:`Plan.stage_layers` layers (apportioned to member
    speed), every microbatch visits every stage, and the steady-state
    tick rate is set by the SLOWEST member's stage time — the pipeline
    analogue of the slowest-member roofline."""
    pp = plan.pp
    specs = fleet.specs[:pp]
    layers = (plan.stage_layers if plan.stage_layers
              else (1,) * pp)
    total_layers = max(sum(layers), 1)
    micro_n = plan.micro
    micro_b = global_batch / max(micro_n, 1)
    w_itemsize = prof.half_itemsize or 4
    _keep, rec_frac = REMAT_POLICIES[plan.remat]

    bound_s, bound_i, bound_compute, bound_hbm = 0.0, 0, 0.0, 0.0
    for i, spec_i in enumerate(specs):
        frac = layers[i] / total_layers
        div = pp if spec_i.shared_host else 1
        flops = ((prof.flops_per_example * global_batch
                  + micro_n * prof.flops_fixed) * frac
                 * (1.0 + rec_frac))
        compute_s = flops / (spec_i.sustained_flops() / div)
        weight_traffic = (micro_n * prof.n_params * w_itemsize * frac)
        hbm_bytes = ((prof.hbm_bytes_per_example * global_batch
                      * (1.0 + rec_frac)
                      + micro_n * prof.hbm_bytes_fixed) * frac
                     + weight_traffic)
        hbm_s = max(hbm_bytes, 0.0) / (spec_i.hbm_bw / div)
        member_s = max(compute_s, hbm_s)
        if member_s > bound_s:
            bound_s, bound_i = member_s, i
            bound_compute, bound_hbm = compute_s, hbm_s

    # the slowest stage paces every tick; warmup/drain bubbles add
    # (pp-1) of its tick times on top of the micro_n steady ticks
    bubble_mult = (micro_n + pp - 1) / max(micro_n, 1)
    step_s = bound_s * bubble_mult

    link = dataclasses.replace(
        fleet.slowest(),
        ici_bw=min(s.ici_bw for s in specs),
        ici_latency_s=max(s.ici_latency_s for s in specs))
    boundary = _pp_boundary_bytes(plan, prof, micro_b)
    hop_s = boundary / link.ici_bw + link.ici_latency_s
    coll_s = 2 * micro_n * hop_s
    colls = [f"stage-boundary ppermute ({_mib(boundary)}/hop, "
             f"2×{micro_n} hops/step) over {plan.pp_axis or 'pp'}({pp}) "
             f"at the slowest link"]
    coll_s += _ring_all_reduce_s(prof.param_bytes_fp32, pp, link)
    colls.append(f"stage-grad assembly psum "
                 f"({_mib(prof.param_bytes_fp32)} stacked) over "
                 f"{plan.pp_axis or 'pp'}({pp})")

    overhead_s = micro_n * max(s.overhead_s for s in specs)
    total_s = step_s + coll_s + overhead_s
    bd = [("compute_ms", bound_compute * bubble_mult * 1e3),
          ("hbm_ms", bound_hbm * bubble_mult * 1e3),
          ("collective_ms", coll_s * 1e3),
          ("overhead_ms", overhead_s * 1e3),
          ("bound_member", float(bound_i)),
          ("stage_ms_bound", bound_s * 1e3),
          ("pp_ticks", float(micro_n + 2 * (pp - 1))),
          ("bubble_frac", (pp - 1) / (micro_n + pp - 1))]
    return total_s * 1e3, bd, colls, ()


def _mib(b):
    return f"{b / 2**20:.1f} MiB"


# ---------------------------------------------------------------------------
# Enumeration + ranking
# ---------------------------------------------------------------------------


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_plans(n_devices: int, *, chunked_loss=False,
                    accum_max: int = 32, global_batch: int):
    """Yield the raw candidate space as a JOINT enumeration (not a
    per-axis sweep): every mesh/zero/accum/chunk point is crossed with
    the remat ladder × offload ladder, dp-only meshes additionally
    carry an expert-parallel twin (``ep == dp`` — one expert per
    device), and pure-pipeline meshes (``pp`` stages × ``micro``
    microbatches) join the space crossed with the same remat × offload
    rungs.  Infeasible combinations are NOT filtered here — the
    planner's structural/memory pruning rejects them with stated
    reasons, so the candidate space stays auditable."""
    meshes = set()
    for dp in _divisors(n_devices):
        rest = n_devices // dp
        for sp in _divisors(rest):
            meshes.add((dp, sp, rest // sp))
        meshes.add((dp, 1, 1))       # partial mesh: idle devices allowed
    chunk_opts = (False, True) if chunked_loss is None else (chunked_loss,)
    variants = [(r, oo, oa) for r in REMAT_POLICIES
                for (oo, oa) in OFFLOAD_LADDER]
    for dp, sp, tp in sorted(meshes):
        zero_opts = (0, 1, 3) if (dp > 1 and sp == 1 and tp == 1) else (0,)
        local = global_batch // dp if dp and global_batch % dp == 0 else 1
        ks = [k for k in _divisors(max(local, 1))
              if k <= accum_max and (k & (k - 1)) == 0]
        for zero in zero_opts:
            for k in ks or [1]:
                for ch in chunk_opts:
                    for remat, oo, oa in variants:
                        yield Plan(dp=dp, sp=sp, tp=tp, zero_stage=zero,
                                   accum=k, chunked_loss=ch,
                                   remat=remat, offload_opt=oo,
                                   offload_act=oa, n_devices=n_devices)
                        if dp > 1 and sp == 1 and tp == 1 and zero == 0:
                            # expert-parallel twin: ep rides the dp axis
                            yield Plan(dp=dp, sp=sp, tp=tp,
                                       zero_stage=zero, accum=k,
                                       chunked_loss=ch, ep=dp,
                                       remat=remat, offload_opt=oo,
                                       offload_act=oa,
                                       n_devices=n_devices)
    # pure-pipeline meshes: pp stages over the device axis, micro
    # power-of-two microbatches (the pipeline's accumulation unit)
    for pp in _divisors(n_devices):
        if pp == 1:
            continue
        micros = [m for m in _divisors(max(global_batch, 1))
                  if (m & (m - 1)) == 0 and pp <= m <= accum_max]
        for micro in micros:
            for ch in chunk_opts:
                for remat, oo, oa in variants:
                    yield Plan(pp=pp, micro=micro, chunked_loss=ch,
                               remat=remat, offload_opt=oo,
                               offload_act=oa, n_devices=n_devices)


@dataclasses.dataclass
class PlanReport:
    """Planner output: the ranked feasible plans, and every rejected
    plan with its stated reason — nothing is pruned silently."""
    best: Optional[Plan]
    ranked: list
    rejected: list                      # [(Plan, reason)]
    profile: ModelProfile
    chip: ChipSpec
    global_batch: int
    hbm_cap: float
    fleet: Optional[Fleet] = None
    search_ms: float = 0.0              # wall-clock of the joint search
    explored: int = 0                   # plans enumerated (incl. rejected)
    pruned_oom: int = 0                 # rejected by the HBM model

    def describe(self, top: int = 5) -> str:
        chip_desc = (f"fleet {self.fleet.name()}"
                     if self.fleet is not None and self.fleet.heterogeneous
                     else self.chip.name)
        out = [f"auto-parallel plan report — {chip_desc}, "
               f"global batch {self.global_batch}, HBM cap "
               f"{self.hbm_cap / 2**30:.2f} GiB/device, model "
               f"{self.profile.n_params / 1e6:.2f}M params "
               f"(profile: {self.profile.source})"]
        if self.explored:
            out.append(f"search: {self.explored} plans explored, "
                       f"{self.pruned_oom} pruned by the HBM model, "
                       f"{self.search_ms:.1f} ms")
        if self.best is None:
            out.append("NO FEASIBLE PLAN — every candidate was rejected:")
        else:
            out.append(f"chosen: {self.best.name()}")
            out.append(self.best.describe())
            out.append(f"runners-up (of {len(self.ranked)} feasible):")
            for p in self.ranked[1:top]:
                why = (f"+{p.predicted_ms - self.best.predicted_ms:.3f} ms "
                       f"predicted vs chosen"
                       if p.predicted_ms is not None else "")
                out.append(f"  {p.name():<24} {p.predicted_ms:9.3f} ms  "
                           f"{(p.predicted_hbm or 0) / 2**20:9.1f} MiB  "
                           f"{why}")
        shown = self.rejected[:max(top * 3, 12)]
        if shown:
            out.append(f"rejected ({len(self.rejected)}):")
            for p, reason in shown:
                out.append(f"  {p.name():<24} {reason}")
            if len(self.rejected) > len(shown):
                out.append(f"  ... {len(self.rejected) - len(shown)} more "
                           f"(same reason classes)")
        return "\n".join(out)


def plan_training(model, optimizer, loss_fn: Callable, example_batch, *,
                  devices=None, half_dtype=None,
                  keep_batchnorm_fp32: bool = True,
                  chip: Optional[ChipSpec] = None,
                  hbm_cap_bytes: Optional[float] = None,
                  hbm_reserve: float = HBM_RESERVE,
                  accum_max: int = 32,
                  chunked_loss=False,
                  profile: Optional[ModelProfile] = None,
                  fleet=None) -> PlanReport:
    """Enumerate → prune (memory, capability) → rank (roofline).

    ``chunked_loss``: what the caller's ``loss_fn`` actually is (the
    planner cannot swap it) — pass ``None`` to enumerate both and see
    the lever's predicted effect in the report.

    ``fleet``: a :class:`Fleet`, the ``"v5e:4+v4:4"`` string syntax, or
    a sequence of :class:`ChipSpec` — one per device, planner order.  A
    heterogeneous fleet switches pricing to the slowest-member bound
    with per-device batch shares (:func:`predict_time_fleet`); memory
    feasibility is then checked for the LARGEST share against the
    SMALLEST member's HBM (conservative on both axes).
    """
    flt = _fleet_of(fleet)
    devices = list(devices) if devices is not None else jax.devices()
    spec = chip or (flt.slowest() if flt is not None else
                    chip_spec(devices))
    prof = profile or profile_model(
        model, optimizer, loss_fn, example_batch, half_dtype=half_dtype,
        keep_batchnorm_fp32=keep_batchnorm_fp32)
    global_batch = _global_batch_of(example_batch)
    if hbm_cap_bytes is not None:
        cap = hbm_cap_bytes
    elif flt is not None:
        cap = min(s.hbm_bytes for s in flt.specs) * (1.0 - hbm_reserve)
    else:
        cap = spec.hbm_bytes * (1.0 - hbm_reserve)
    n_plan_devices = flt.n_devices if flt is not None else len(devices)

    hetero = flt is not None and flt.heterogeneous
    feasible, rejected = [], []
    explored = 0
    t_search = time.perf_counter()
    for plan in enumerate_plans(n_plan_devices, chunked_loss=chunked_loss,
                                accum_max=accum_max,
                                global_batch=global_batch):
        explored += 1
        reason = _structural_reject(plan, prof, global_batch, fleet=flt)
        if reason is not None:
            rejected.append((plan, reason))
            continue
        plan = dataclasses.replace(
            plan,
            tp_axis=prof.tp_axis if plan.tp > 1 else None,
            sp_axis=prof.sp_axis if plan.sp > 1 else None,
            pp_axis=prof.pp_axis if plan.pp > 1 else None,
            dp_axis=(prof.moe_axis if plan.ep > 1 and prof.moe_axis
                     else plan.dp_axis))
        if hetero and plan.pp > 1:
            # heterogeneous pipeline: stages apportioned to member
            # speed (faster chips take more layers); the batch is NOT
            # split — every microbatch visits every stage
            members = flt.specs[:plan.pp]
            n_layers = prof.layers or plan.pp
            plan = dataclasses.replace(
                plan,
                stage_layers=apportion_shares(
                    [s.sustained_flops() for s in members], n_layers),
                stage_members=tuple(s.name for s in members))
            shares, mem_batch = None, global_batch
        elif hetero:
            # memory for the binding member: the largest share on the
            # smallest HBM — price the uniform formula at an effective
            # global batch of max_share × dp so micro_b == max_share
            shares = apportion_shares(
                [s.sustained_flops() for s in flt.specs[:plan.n_used]],
                global_batch)
            mem_batch = max(shares) * plan.dp
        else:
            shares, mem_batch = None, global_batch
        mem, mem_bd = predict_memory(plan, prof, spec, mem_batch)
        if mem > cap:
            over = dict(mem_bd)
            reason = (
                f"memory-infeasible: needs {mem / 2**20:.1f} MiB/device > "
                f"cap {cap / 2**20:.1f} MiB (masters "
                f"{over['mem_masters'] / 2**20:.1f} + slots "
                f"{over['mem_slots'] / 2**20:.1f} + half "
                f"{over['mem_half'] / 2**20:.1f} + grads "
                f"{over['mem_grads'] / 2**20:.1f} + acts "
                f"{over['mem_acts'] / 2**20:.1f} + batch "
                f"{over['mem_batch'] / 2**20:.1f})")
            rejected.append((dataclasses.replace(
                plan, predicted_hbm=mem, breakdown=tuple(mem_bd)), reason))
            continue
        if hetero:
            ms, time_bd, colls, shares = predict_time_fleet(
                plan, prof, flt, global_batch, shares=shares)
        else:
            ms, time_bd, colls = predict_time(plan, prof, spec,
                                              global_batch)
        plan = dataclasses.replace(
            plan, predicted_ms=ms, predicted_hbm=mem,
            breakdown=tuple(time_bd + mem_bd), collectives=tuple(colls),
            device_shares=tuple(shares) if shares is not None else ())
        feasible.append(plan)

    # deterministic rank: predicted time, then fewer devices, lower
    # stage, smaller K, simpler v3 levers (simpler plans win ties)
    def _rank(p):
        return (p.predicted_ms, p.n_used, p.zero_stage, p.accum, p.tp,
                p.sp, p.pp, p.micro, _REMAT_ORDER.get(p.remat, 9),
                p.offload_opt, p.offload_act, p.ep)

    feasible.sort(key=_rank)
    search_ms = (time.perf_counter() - t_search) * 1e3
    pruned_oom = sum(1 for _, r in rejected
                     if r.startswith("memory-infeasible"))
    best = feasible[0] if feasible else None
    _obs.gauge("plan.search_ms").set(search_ms)
    _obs.gauge("plan.explored").set(float(explored))
    _obs.gauge("plan.pruned_oom").set(float(pruned_oom))
    if best is not None and best.pp > 1:
        bf = dict(best.breakdown).get("bubble_frac")
        if bf is not None:
            _obs.gauge("plan.bubble_frac").set(float(bf))
    return PlanReport(best=best,
                      ranked=feasible, rejected=rejected, profile=prof,
                      chip=spec, global_batch=global_batch, hbm_cap=cap,
                      fleet=flt, search_ms=search_ms, explored=explored,
                      pruned_oom=pruned_oom)


def _structural_reject(plan: Plan, prof: ModelProfile,
                       global_batch: int,
                       fleet: Optional[Fleet] = None) -> Optional[str]:
    if fleet is not None and fleet.heterogeneous and \
            (plan.tp > 1 or plan.sp > 1):
        return (f"tp={plan.tp}/sp={plan.sp} across the mixed fleet "
                f"{fleet.name()}: tensor/sequence parallelism needs "
                f"identical per-shard throughput (lockstep layer math), "
                f"so heterogeneous fleets are dp-only — stragglers are "
                f"absorbed by batch shares, not layer shards")
    if plan.dp > 1 and global_batch % plan.dp:
        return (f"global batch {global_batch} not divisible by "
                f"dp={plan.dp}")
    if plan.tp > 1:
        if prof.tp_axis is None:
            return (f"tp={plan.tp} needs a model built with tp_axis= "
                    f"(this one was built unsharded — rebuild with "
                    f"tp_axis='tp' to enable tensor parallelism)")
        if prof.heads and prof.heads % plan.tp:
            return (f"tp={plan.tp} does not divide the model's "
                    f"{prof.heads} attention heads")
    if plan.sp > 1:
        if prof.sp_axis is None:
            return (f"sp={plan.sp} needs a model built with sp_axis= "
                    f"(ring attention) — rebuild to enable sequence "
                    f"parallelism")
        if prof.seq_len and prof.seq_len % plan.sp:
            return (f"sp={plan.sp} does not divide sequence length "
                    f"{prof.seq_len}")
    if plan.chunked_loss and not prof.logits_bytes_per_example:
        return ("chunked_loss priced but the model exposes no vocab head "
                "(no logits working set to chunk)")
    if plan.micro > 1 and plan.pp == 1:
        return (f"micro={plan.micro} without pipeline stages — the "
                f"microbatch axis is the pipeline's accumulation unit "
                f"(use accum=K for non-pipelined accumulation)")
    if plan.pp > 1:
        if prof.pp_axis is None:
            return (f"pp={plan.pp} needs a PipelinedStack model (build "
                    f"one with parallel.pipeline.PipelinedStack to "
                    f"enable pipeline parallelism)")
        if plan.tp > 1 or plan.sp > 1 or plan.zero_stage:
            return (f"pp={plan.pp} composes with neither tp/sp shard "
                    f"axes nor ZeRO in this planner — pipeline plans "
                    f"run pure pp")
        if plan.micro < plan.pp:
            return (f"micro={plan.micro} < pp={plan.pp}: the pipeline "
                    f"never fills (every tick would carry a bubble)")
        if global_batch % (plan.dp * plan.micro):
            return (f"global batch {global_batch} not divisible by "
                    f"dp×micro = {plan.dp * plan.micro}")
        hetero = fleet is not None and fleet.heterogeneous
        if hetero and plan.dp > 1:
            return (f"dp×pp across the mixed fleet {fleet.name()}: "
                    f"heterogeneous pipelines absorb stragglers via "
                    f"stage apportionment, dp replicas would need "
                    f"identical stage sets")
        if not hetero and prof.layers and prof.layers % plan.pp:
            return (f"pp={plan.pp} does not divide the model's "
                    f"{prof.layers} layers (homogeneous stages)")
    if plan.remat != "none" and plan.pp == 1 and not prof.remat_capable:
        return (f"remat={plan.remat} needs a model built with "
                f"remat=True (activation checkpointing) — rebuild to "
                f"enable it")
    if plan.ep > 1:
        if prof.moe_axis is None:
            return (f"ep={plan.ep} needs a switch-MoE model (build with "
                    f"moe_axis=/moe_num_experts= to enable expert "
                    f"parallelism)")
        if plan.ep != plan.dp:
            return (f"ep={plan.ep} must equal dp={plan.dp}: expert "
                    f"parallelism rides the data axis (one expert per "
                    f"dp member)")
        if prof.n_experts and plan.ep != prof.n_experts:
            return (f"ep={plan.ep} != the model's {prof.n_experts} "
                    f"experts — switch_moe routes one expert per "
                    f"device along the axis")
        if plan.zero_stage or plan.tp > 1 or plan.sp > 1 or plan.pp > 1:
            return (f"ep={plan.ep} runs the explicit-axis MoE path: no "
                    f"ZeRO/tp/sp/pp composition in this planner")
        if fleet is not None and fleet.heterogeneous:
            return (f"ep={plan.ep} across the mixed fleet "
                    f"{fleet.name()}: expert dispatch needs lockstep "
                    f"all-to-all throughput")
    return None


# ---------------------------------------------------------------------------
# Applying a plan: thread the existing knobs / wrap the explicit-axis path
# ---------------------------------------------------------------------------


def _resolve_devices(devices):
    if devices is None:
        return list(jax.devices())
    if isinstance(devices, int):
        ds = list(jax.devices())
        if devices > len(ds):
            raise ValueError(f"asked to plan for {devices} devices, "
                             f"have {len(ds)}")
        return ds[:devices]
    return list(devices)


def apply_plan(plan: Plan, model, optimizer, loss_fn, devices=None,
               **base_kwargs):
    """Build the train step a plan describes by threading the existing
    make_train_step knobs (dp/ZeRO plans run the GSPMD global-view path,
    tp/sp plans the explicit shard_map path).  The returned step carries
    ``.plan``."""
    from ..training.step import make_train_step
    devices = _resolve_devices(devices)
    if plan.n_used > len(devices):
        raise ValueError(f"plan {plan.name()} needs {plan.n_used} devices, "
                         f"have {len(devices)}")
    kw = dict(base_kwargs)
    kw.pop("parallel", None)
    for knob in ("axis_name", "tp_axis", "zero_sharding", "zero_mesh"):
        if kw.pop(knob, None):
            raise ValueError(
                f"parallel= owns the {knob} knob — pass one or the other")
    if plan.pp > 1:
        return _apply_pp_plan(plan, model, optimizer, loss_fn, devices, kw)
    kw.update(plan.step_kwargs(devices))

    if plan.tp == 1 and plan.sp == 1 and plan.ep == 1:
        step = make_train_step(model, optimizer, loss_fn, _plan=plan, **kw)
        step.plan = plan
        return step

    # explicit-axis path: the tested shard_map wrap (tp / sp / dp×tp / ep)
    if plan.ep > 1 and \
            getattr(model, "moe_axis", None) != plan.dp_axis:
        raise ValueError(
            f"plan {plan.name()} routes {plan.ep} experts over axis "
            f"{plan.dp_axis!r} but the model's moe_axis is "
            f"{getattr(model, 'moe_axis', None)!r} — build the model "
            f"with moe_axis={plan.dp_axis!r} (expert dispatch rides the "
            f"data axis)")
    if plan.tp > 1 and getattr(model, "tp_axis", None) is None:
        raise ValueError(
            f"plan {plan.name()} uses tensor parallelism but the model "
            f"was built without tp_axis= — rebuild the model with "
            f"tp_axis={plan.tp_axis or 'tp'!r}")
    if plan.sp > 1 and getattr(model, "sp_axis", None) is None:
        raise ValueError(
            f"plan {plan.name()} uses sequence parallelism but the model "
            f"was built without sp_axis= — rebuild the model with "
            f"sp_axis={plan.sp_axis or 'sp'!r}")
    donate = bool(kw.get("donate_state", True))
    step = make_train_step(model, optimizer, loss_fn, _plan=plan, **kw)
    axis_dims = [(plan.dp_axis, plan.dp)]
    if plan.sp > 1:
        axis_dims.append((model.sp_axis, plan.sp))
    if plan.tp > 1:
        axis_dims.append((model.tp_axis, plan.tp))
    axis_dims = [(n, s) for n, s in axis_dims if s > 1] or \
        [(plan.dp_axis, 1)]
    names = tuple(n for n, _ in axis_dims)
    shape = tuple(s for _, s in axis_dims)
    mesh = Mesh(np.array(devices[:plan.n_used]).reshape(shape), names)
    mean_axes = tuple(n for n, s in axis_dims
                      if s > 1 and n != (model.tp_axis if plan.tp > 1
                                         else None))

    from .. import compat
    from ..runtime import executor as _executor

    raw = step._raw_step_fn
    plan_key = plan.key()
    token = next(_PLAN_TOKENS)
    dispatch_no = itertools.count(1)
    programs = {}

    def _batch_spec(el):
        def leaf(a):
            dims = []
            if plan.dp > 1 and getattr(a, "ndim", 0) >= 1:
                dims.append(plan.dp_axis)
            else:
                dims.append(None)
            if plan.sp > 1 and getattr(a, "ndim", 0) >= 2:
                dims.append(model.sp_axis)
            return P(*dims)
        return jax.tree_util.tree_map(leaf, el)

    def _program(specs):
        prog = programs.get(specs)
        if prog is not None:
            return prog

        def run(state, *b):
            new_state, loss = raw(state, *b)
            if mean_axes:
                # the in-step loss is one shard's local mean; make
                # the reported number the global mean (grads are
                # already psum-exchanged inside the step)
                loss = jax.lax.pmean(
                    loss, mean_axes if len(mean_axes) > 1
                    else mean_axes[0])
            return new_state, loss

        def wrap(f):
            return compat.shard_map(f, mesh=mesh,
                                    in_specs=(P(),) + specs,
                                    out_specs=(P(), P()), check_vma=False)

        prog = _executor.Program(
            "train_step", (token, plan_key, specs, donate), run,
            donate_argnums=(0,) if donate else (), wrap=wrap)
        programs[specs] = prog
        return prog

    def dispatch(state, *batch):
        specs = tuple(_batch_spec(b) for b in batch)
        return _executor.executor.submit(
            _program(specs), (state,) + batch, step=next(dispatch_no))

    step._step_fn = dispatch
    step._via_executor = True
    step.plan = plan
    return step


_PIPELINE_STEP_KNOBS = ("half_dtype", "dynamic_loss_scale", "scale_window",
                        "min_loss_scale", "max_loss_scale", "loss_scale",
                        "lr_schedule")


def _apply_pp_plan(plan: Plan, model, optimizer, loss_fn, devices, kw):
    """Pipeline plans: route to the tested pipeline entry points
    (make_pipeline_train_step for 1F1B, the GPipe stack wrap of
    make_train_step otherwise) and dispatch the sharded step through the
    executor over a 1-D pp mesh with the batch replicated — the same
    wrap tests/test_pipeline.py drives by hand."""
    from ..training.step import make_train_step
    from .pipeline import make_pipeline_train_step
    from .. import compat
    from ..runtime import executor as _executor

    if getattr(model, "n_micro", None) is None or \
            getattr(model, "stage_fn", None) is None:
        raise ValueError(
            f"plan {plan.name()} pipelines {plan.pp} stages but the model "
            f"is not a PipelinedStack — build one with "
            f"PipelinedStack(stage_fn, stacked_params, axis_name, "
            f"n_micro={plan.micro})")
    if plan.dp > 1 or plan.tp > 1 or plan.sp > 1 or plan.ep > 1:
        raise ValueError(
            f"plan {plan.name()}: the planner schedules pure pipelines "
            f"only — no dp/tp/sp/ep composition with pp")
    if model.n_micro != plan.micro:
        raise ValueError(
            f"plan {plan.name()} schedules micro={plan.micro} microbatches "
            f"but the stack was built with n_micro={model.n_micro} — "
            f"rebuild the stack to match the plan")
    axis = plan.pp_axis or model.axis_name
    if model.axis_name != axis:
        raise ValueError(
            f"plan {plan.name()} pipelines over axis {axis!r} but the "
            f"stack's axis_name is {model.axis_name!r}")
    step_kw = {k: v for k, v in kw.items() if k in _PIPELINE_STEP_KNOBS}
    unknown = {k for k in kw if k not in _PIPELINE_STEP_KNOBS
               and k not in ("donate_state",)}
    if unknown:
        raise ValueError(
            f"plan {plan.name()}: pipeline steps do not accept "
            f"{sorted(unknown)} — supported knobs: "
            f"{sorted(_PIPELINE_STEP_KNOBS)}")

    if plan.remat == "full":
        # 1F1B recomputes stage forwards by construction
        step = make_pipeline_train_step(model, optimizer, loss_fn,
                                        schedule="1f1b", **step_kw)
    else:
        if plan.remat == "selective" and not model.remat_stage:
            raise ValueError(
                f"plan {plan.name()} checkpoints stage internals "
                f"(remat=selective) but the stack was built with "
                f"remat_stage=False — rebuild with remat_stage=True")
        if plan.remat == "none" and model.remat_stage:
            raise ValueError(
                f"plan {plan.name()} keeps all activations (remat=none) "
                f"but the stack was built with remat_stage=True — the "
                f"run would not match the plan's memory model")
        step = make_train_step(model, optimizer, loss_fn, _plan=plan,
                               tp_axis=axis, **step_kw)

    donate = bool(kw.get("donate_state", True)) and plan.remat != "full"
    mesh = Mesh(np.array(devices[:plan.pp]), (axis,))
    raw = step._raw_step_fn
    plan_key = plan.key()
    token = next(_PLAN_TOKENS)
    dispatch_no = itertools.count(1)
    programs = {}

    def _program(nbatch):
        prog = programs.get(nbatch)
        if prog is not None:
            return prog

        def wrap(f):
            # batch replicated: every stage sees the full batch; the
            # scan/1f1b schedule slices its own microbatches
            return compat.shard_map(
                f, mesh=mesh, in_specs=(P(),) * (1 + nbatch),
                out_specs=(P(), P()), check_vma=False)

        prog = _executor.Program(
            "train_step", (token, plan_key, nbatch, donate), raw,
            donate_argnums=(0,) if donate else (), wrap=wrap)
        programs[nbatch] = prog
        return prog

    def dispatch(state, *batch):
        return _executor.executor.submit(
            _program(len(batch)), (state,) + batch,
            step=next(dispatch_no))

    step._step_fn = dispatch
    step._via_executor = True
    step.plan = plan
    return step


# ---------------------------------------------------------------------------
# Measured refinement (auto_tune) + the make_train_step entry point
# ---------------------------------------------------------------------------


def _concrete_batch(example_batch):
    """Concrete arrays for trial runs: the example's own arrays where
    concrete, zeros of the right shape/dtype where abstract."""
    def leaf(a):
        if isinstance(a, jax.ShapeDtypeStruct):
            return jnp.zeros(a.shape, a.dtype)
        return jnp.asarray(a)
    return tuple(jax.tree_util.tree_map(leaf, el) for el in example_batch)


def measure_plan(plan: Plan, model, optimizer, loss_fn, example_batch,
                 devices=None, steps: int = 3, **base_kwargs):
    """Compile + time a plan through the real step (the step-program
    cache does the compiling).  Returns min ms/step over ``steps`` timed
    calls, or None with the failure recorded on the exception."""
    batch = _concrete_batch(example_batch)
    step = apply_plan(plan, model, optimizer, loss_fn, devices=devices,
                      **base_kwargs)
    float(step(*batch))              # compile + warm
    best = math.inf
    for _ in range(max(steps, 1)):
        t0 = time.perf_counter()
        float(step(*batch))          # scalar fetch = device sync
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def auto_tune_report(report: PlanReport, model, optimizer, loss_fn,
                     example_batch, devices=None, k: int = 3,
                     steps: int = 3, **base_kwargs) -> PlanReport:
    """Measured refinement: compile and time the top-k predicted plans
    and re-rank by measurement (prediction breaks ties / fills gaps)."""
    measured = []
    for plan in report.ranked[:max(k, 1)]:
        try:
            ms = measure_plan(plan, model, optimizer, loss_fn,
                              example_batch, devices=devices, steps=steps,
                              **base_kwargs)
            measured.append(dataclasses.replace(plan, measured_ms=ms))
            _obs.event("plan.auto_tune", plan=plan.name(),
                       plan_key=plan.key(), measured_ms=ms,
                       predicted_ms=plan.predicted_ms)
        except Exception as e:        # a plan that fails to run loses
            report.rejected.append(
                (plan, f"auto_tune trial failed: {type(e).__name__}: {e}"))
            _obs.event("plan.auto_tune", plan=plan.name(),
                       plan_key=plan.key(), measured_ms=None,
                       error=f"{type(e).__name__}: {e}")
    measured.sort(key=lambda p: (p.measured_ms, p.predicted_ms))
    ranked = measured + [p for p in report.ranked
                         if p.key() not in {m.key() for m in measured}]
    return dataclasses.replace(
        report, best=ranked[0] if ranked else None, ranked=ranked)


def build_planned_step(model, optimizer, loss_fn, parallel, *,
                       example_batch=None, devices=None, auto_tune: int = 0,
                       plan_options=None, **base_kwargs):
    """The ``make_train_step(parallel=...)`` entry point: resolve
    "auto" (or a Plan) into knobs and build the step.  The returned step
    carries ``.plan`` and (for "auto") ``.plan_report``."""
    devices = _resolve_devices(devices)
    report = None
    if isinstance(parallel, str):
        if parallel != "auto":
            raise ValueError(
                f"parallel= accepts 'auto' or a parallel.auto.Plan, "
                f"got {parallel!r}")
        if example_batch is None:
            raise ValueError(
                "parallel='auto' needs example_batch=(x, y, ...) — a "
                "tuple of arrays (or ShapeDtypeStructs) shaped like one "
                "global training batch, so the planner knows the batch "
                "and sequence geometry")
        opts = dict(plan_options or {})
        report = plan_training(
            model, optimizer, loss_fn, example_batch, devices=devices,
            half_dtype=base_kwargs.get("half_dtype"),
            keep_batchnorm_fp32=base_kwargs.get("keep_batchnorm_fp32",
                                                True),
            **opts)
        if report.best is None:
            raise RuntimeError(
                "parallel='auto': no feasible plan\n" + report.describe())
        if auto_tune:
            report = auto_tune_report(
                report, model, optimizer, loss_fn, example_batch,
                devices=devices, k=auto_tune, **base_kwargs)
            if report.best is None:
                raise RuntimeError(
                    "parallel='auto': every auto_tune trial failed\n"
                    + report.describe())
        plan = report.best
    elif isinstance(parallel, Plan):
        plan = parallel
    else:
        raise TypeError(
            f"parallel= accepts 'auto' or a parallel.auto.Plan, got "
            f"{type(parallel).__name__}")
    _obs.event("plan.decision", plan=plan.name(), plan_key=plan.key(),
               source="auto" if report is not None else "explicit",
               n_devices=len(devices),
               predicted_ms=plan.predicted_ms,
               measured_ms=plan.measured_ms,
               feasible=len(report.ranked) if report is not None else None,
               rejected=len(report.rejected) if report is not None else None)
    step = apply_plan(plan, model, optimizer, loss_fn, devices=devices,
                      **base_kwargs)
    step.plan_report = report
    return step


def measured_step_memory(compiled) -> int:
    """Per-device footprint of a compiled step program, donation-aware:
    arguments + outputs + temps − aliased (donated buffers counted
    once).  The validation target for :func:`predict_memory`."""
    ma = compiled.memory_analysis()
    return int(ma.argument_size_in_bytes + ma.output_size_in_bytes
               + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
