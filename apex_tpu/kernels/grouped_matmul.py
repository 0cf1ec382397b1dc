"""grouped_matmul: the matrix products of routed experts.

Rows sorted by the expert they go to, each expert's rows starting at a
tile boundary (:func:`tile_layout`): a tile of rows then belongs to one
expert, and the product is a tiled matmul whose right-hand block is
chosen per row tile — the expert's matrix is streamed once for the
tiles that use it, and tiles past the last used one are skipped without
moving anything.

* :func:`tile_layout` — from each pair's group to the rows' layout.
* :func:`grouped_matmul` — ``lhs (M, K)`` x ``rhs (G, K, N)`` by that
  layout (or ``rhs (G, N, K)``, each matrix kept the other way round):
  the registered ``routed_experts`` kernel (its name in a device trace),
  with ``jax.lax.ragged_dot`` as its XLA tier.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import choose, pallas_mode, register_kernel

_f32 = jnp.float32

#: rows of a tile of the Pallas tier (the MXU's height); the XLA tier
#: needs no alignment and lays the rows out with tiles of one row
TILE_ROWS = 128
#: the widest K and N tile (2 MiB of bf16 a block, two in flight)
_TILE_KN_MAX = 1024
#: the widest width taken whole where no tile divides it (a block of
#: 1856 x 896 bf16 is 3.2 MiB, two in flight)
_WHOLE_MAX = 2048


class TileLayout(NamedTuple):
    """Where the pairs sorted by group lie, ``tile`` rows a tile."""
    tile: int               # rows a tile (static)
    sizes: jax.Array        # (G,) pairs of each group
    padded: jax.Array       # (G,) the same, rounded up to whole tiles
    tile_group: jax.Array   # (n_tiles,) the group a row tile belongs to
    n_active: jax.Array     # () tiles in use (a prefix of the tiles)
    pair_of_row: jax.Array  # (M,) index of the pair a row holds, -1: none
    row_of_pair: jax.Array  # (P,) row of each pair, -1: its group is not held


def tile_layout(group, n_groups: int, max_rows: int, tile: int) -> TileLayout:
    """``group (P,)``: the held group each pair goes to, ``n_groups`` for
    a pair that goes elsewhere.  At most ``max_rows`` pairs go to held
    groups (the caller's bound); the layout has
    ``ceil(max_rows / tile) + n_groups`` tiles, enough for every group to
    end in a partly filled one."""
    p = group.shape[0]
    g_ids = jnp.arange(n_groups, dtype=jnp.int32)
    sizes = jnp.sum(group[:, None] == g_ids[None, :], axis=0,
                    dtype=jnp.int32)                         # (G,)
    tiles = (sizes + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile                    # (G,)
    sorted_start = jnp.cumsum(sizes) - sizes
    n_tiles = -(-max_rows // tile) + n_groups
    tile_group = jnp.minimum(
        jnp.sum(jnp.arange(n_tiles, dtype=jnp.int32)[:, None]
                >= tile_end[None, :], axis=1, dtype=jnp.int32),
        n_groups - 1)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)  # sorted pairs
    rank = jnp.zeros((p,), jnp.int32).at[order].set(
        jnp.arange(p, dtype=jnp.int32))                  # place when sorted
    held = group < n_groups
    g_safe = jnp.minimum(group, n_groups - 1)
    row_of_pair = jnp.where(
        held, row_start[g_safe] + rank - sorted_start[g_safe], -1)
    row = jnp.arange(n_tiles * tile, dtype=jnp.int32)
    g_row = tile_group[row // tile]
    k = row - row_start[g_row]
    filled = (row // tile < tile_end[-1]) & (k < sizes[g_row])
    pair_of_row = jnp.where(
        filled, order[jnp.clip(sorted_start[g_row] + k, 0, p - 1)], -1)
    return TileLayout(tile, sizes, tiles * tile, tile_group, tile_end[-1],
                      pair_of_row, row_of_pair)


def _tile_of(n: int):
    """The widest tile of whole lane rows that divides ``n``: 1024 of
    7168 or 4096, 768 of 2304, 896 of 1792, 896 or 2688 (a grid step
    costs about the same whatever it moves, so powers of two alone would
    stream a 2304 x 1792 matrix in 63 blocks of 128 KiB where 6 of 1.3
    MiB do).  Where none divides it (1856 is 14.5 lane rows) the whole
    width is one block, which is legal whatever its lane count since it
    is as wide as its array, if it is more than a lane row, whole
    sublane tiles, and no wider than :data:`_WHOLE_MAX`."""
    whole = n % 8 == 0 and 128 < n <= _WHOLE_MAX
    return next((t for t in range(_TILE_KN_MAX, 0, -128) if n % t == 0),
                n if whole else None)


def _gmm_kernel(tile_group_ref, n_active_ref, lhs_ref, rhs_ref, out_ref,
                acc_ref, *, nk, transposed):
    i, k = pl.program_id(0), pl.program_id(2)
    active = i < n_active_ref[0]

    @pl.when(active & (k == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _():
        if transposed:      # rhs block (tn, tk): both contracted on K
            acc_ref[...] += jax.lax.dot_general(
                lhs_ref[...], rhs_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=_f32)
        else:
            acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                                    preferred_element_type=_f32)

    @pl.when(active & (k == nk - 1))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret",
                                             "transposed"))
def _gmm_call(tile_group, n_active, lhs, rhs, *, tile, interpret,
              transposed=False):
    m, kdim = lhs.shape
    n = rhs.shape[1 if transposed else 2]
    tk, tn = _tile_of(kdim), _tile_of(n)
    nj, nk = n // tn, kdim // tk

    # a tile past the last one in use keeps every block index of the
    # last step that did work, so that nothing is fetched or written for it
    def at(i, j, k, na):
        act = i < na[0]
        return (jnp.maximum(jnp.minimum(i, na[0] - 1), 0),
                jnp.where(act, j, nj - 1), jnp.where(act, k, nk - 1))

    def lhs_at(i, j, k, tg, na):
        ic, _, kc = at(i, j, k, na)
        return ic, kc

    def rhs_at(i, j, k, tg, na):
        ic, jc, kc = at(i, j, k, na)
        return (tg[ic], jc, kc) if transposed else (tg[ic], kc, jc)

    def out_at(i, j, k, tg, na):
        ic, jc, _ = at(i, j, k, na)
        return ic, jc

    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk, transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tile, nj, nk),
            in_specs=[pl.BlockSpec((tile, tk), lhs_at),
                      pl.BlockSpec((None, tn, tk) if transposed
                                   else (None, tk, tn), rhs_at)],
            out_specs=pl.BlockSpec((tile, tn), out_at),
            scratch_shapes=[pltpu.VMEM((tile, tn), _f32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            **_vmem(tile, tk, tn, lhs.dtype)),
        interpret=interpret,
        name="routed_experts",
    )(tile_group, n_active.reshape(1), lhs, rhs)


def _vmem(tile, tk, tn, dtype) -> dict:
    """The scoped-VMEM limit where a block is a whole width past the
    widest tile: both operands' and the result's blocks two in flight,
    the accumulator, and as much again for the compiler's own.  The
    tiled widths keep the default limit (and their program)."""
    size = jnp.dtype(dtype).itemsize
    need = 2 * size * (tile * tk + tk * tn + tile * tn) + 4 * tile * tn
    return {"vmem_limit_bytes": max(32 << 20, 2 * need)} \
        if max(tk, tn) > _TILE_KN_MAX else {}


def takes_tiles(kdim: int, n: int, dtype) -> bool:
    """Whether the Pallas tier's tiles fit these widths: the caller lays
    its rows out with :data:`TILE_ROWS` then, else with tiles of one."""
    return pallas_mode() is not None and _tile_of(kdim) is not None \
        and _tile_of(n) is not None \
        and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(_f32))


def grouped_matmul(lhs, rhs, layout: TileLayout, transposed=False):
    """``lhs (M, K)`` rows laid out by ``layout`` times ``rhs (G, K, N)``
    -> ``(M, N)``: each row with its own group's matrix.  ``transposed``:
    ``rhs`` is ``(G, N, K)``, each matrix ``(out, in)``, and is read
    where it lies (both operands contracted on their minor dimension).
    Rows no pair holds come back undefined in the Pallas tier and zero
    in the XLA tier: the caller selects by ``layout.row_of_pair``."""
    mode = kernel_mode(lhs, rhs, layout.tile, transposed)
    if mode is not None:
        return _gmm_call(layout.tile_group, layout.n_active, lhs,
                         rhs.astype(lhs.dtype), tile=layout.tile,
                         interpret=mode == "interpret",
                         transposed=transposed)
    if transposed:
        rhs = jnp.swapaxes(rhs, 1, 2)
    return _gmm_xla(lhs, rhs, layout.padded)


def kernel_mode(lhs, rhs, tile, transposed=False):
    """The rule: the mode the kernel runs in, or ``None`` for the XLA
    tier.  The kernel streams each used expert's matrix once and skips
    the unused tiles, so it is taken wherever the rows were laid out for
    it and its tiles fit (PERF.md section 6, PR 29)."""
    return choose("routed_experts", fits=tile == TILE_ROWS and takes_tiles(
        lhs.shape[1], rhs.shape[1 if transposed else 2], lhs.dtype))


def _gmm_xla(lhs, rhs, padded):
    """The XLA tier (the declared fallback)."""
    exact = lhs.dtype == jnp.bfloat16
    return jax.lax.ragged_dot(
        lhs, rhs.astype(lhs.dtype), padded,
        precision=None if exact else jax.lax.Precision.HIGHEST,
        preferred_element_type=_f32).astype(lhs.dtype)


def _audit_programs():
    sds = jax.ShapeDtypeStruct
    lhs = sds((3 * TILE_ROWS, 128), jnp.bfloat16)
    rhs = sds((2, 128, 256), jnp.bfloat16)
    group = sds((16,), jnp.int32)

    def _pallas(lhs, rhs, group):
        lay = tile_layout(group, 2, TILE_ROWS, TILE_ROWS)
        return _gmm_call(lay.tile_group, lay.n_active, lhs, rhs,
                         tile=TILE_ROWS, interpret=False)

    def _xla(lhs, rhs, group):
        lay = tile_layout(group, 2, TILE_ROWS, TILE_ROWS)
        return _gmm_xla(lhs, rhs, lay.padded)

    return [("pallas", _pallas, (lhs, rhs, group)),
            ("xla", _xla, (lhs, rhs, group))]


register_kernel(
    "routed_experts",
    xla_fallback="apex_tpu.kernels.grouped_matmul._gmm_xla",
    doc="Grouped matmul of routed experts: rows sorted by expert",
    audit_programs=_audit_programs)
