"""hyper_connection: the mapping of a manifold-constrained hyper-connection
(mHC), the residual path of a model whose residual is ``n`` streams.

A token's residual state is ``X (n, C)`` float32, kept as one row of
``n*C`` (stream ``i`` in columns ``i*C .. (i+1)*C``).  A sublayer ``F``
has its own ``Phi ((2n + n*n), n*C)`` (stored ``(out, in)``), three
scalars ``alpha = (a_pre, a_post, a_res)`` and a bias ``b`` of ``2n +
n*n``::

    r       = X / sqrt(mean(X^2) + eps)           # over all n*C, no gain
    [p|q|R] = Phi r                               # n + n + n*n
    H_pre   = sigmoid(a_pre p + b_pre)
    H_post  = 2 sigmoid(a_post q + b_post)
    M       = exp(clamp(a_res mat(R) + b_res))    # mat: row-major (n, n)
    repeat iters: M /= rowsum(M) + eps; M /= colsum(M) + eps
    h       = sum_i H_pre[i] X[i]                 # what F is handed
    X'[i]   = sum_j M[i, j] X[j] + H_post[i] F(h)

* :func:`hyper_conn_pre` — ``X`` -> ``(h, mix)``: the norm, the product
  with ``Phi``, the sigmoids, the clamp and exp, every Sinkhorn
  iteration (in full, float32) and ``h``.  ``mix (R, 128)`` float32 holds
  a row's ``H_pre``, ``H_post`` and ``H_res = M`` (:data:`MIX_LANES`).
  Custom call ``hyper_conn_pre``.
* :func:`hyper_conn_post` — ``(X, y, mix)`` -> ``X'``, written where
  ``X`` lies (aliased).  Custom call ``hyper_conn_post``.

**Tiles.** Both calls step over tiles of rows.  The product ``Phi r``
reads ``Phi`` once a call (its block never moves) and takes ``X`` in
two bfloat16 pieces (``X = hi + lo`` to 2^-17) against ``Phi`` as
stored: a float32 result from two passes of the MXU where ``HIGHEST``
takes six (a float32 ``Phi`` is taken at ``HIGHEST``).  The Sinkhorn
iterations run with the tokens on the lanes: a permutation matrix,
applied in the MXU's native form at ``HIGHEST`` (a product by one or by
nought, exact), lays each column ``j`` of ``M`` as the first ``n`` rows
of a sublane tile of its own, so that a row sum is the sum of the tiles
and a column sum is a sum down one tile; the identity takes the result
back to a row a token.  Both tiers lay ``mix`` out alike, so the post
of either tier reads the pre of either.

``tests/test_hyper_connection.py`` holds both tiers to the formula.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import choose, register_kernel

_f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

#: lanes of a row of ``mix``: ``H_pre`` at lanes ``0 .. n``, ``H_post``
#: at ``8 .. 8 + n``, ``H_res[i, j]`` at ``16 + 8 j + i`` (column ``j``
#: of ``M`` in a sublane tile of its own once the tokens are on the
#: lanes), zeros elsewhere
MIX_LANES = 128
_PRE, _POST, _RES = 0, 8, 16

#: rows a grid step: the pre call's is one lane row, so that its
#: Sinkhorn tile ``(128, rows)`` turns back by a square product
_PRE_ROWS = 128
_POST_ROWS = 64


def mix_lane(kind: str, i: int, j: int = 0) -> int:
    """Where ``H_pre[i]`` (``"pre"``), ``H_post[i]`` (``"post"``) or
    ``H_res[i, j]`` (``"res"``) lies in a row of ``mix``."""
    return {"pre": _PRE + i, "post": _POST + i, "res": _RES + 8 * j + i}[kind]


def _check(n):
    if not 1 < n <= 8 or _RES + 8 * n > MIX_LANES:
        raise ValueError(f"hyper_connection: {n} streams; the mix's layout "
                         f"takes 2 .. 8")


def _product(x, phi):
    """``x (R, K) @ phi (N, K)^T`` float32: two bfloat16 passes against a
    bfloat16 ``phi`` (``x = hi + lo``: exact to 2^-17 of ``x``), else at
    ``HIGHEST``."""
    nt = (((1,), (1,)), ((), ()))
    if phi.dtype != jnp.bfloat16:
        return jax.lax.dot_general(x, phi.astype(_f32), nt,
                                   preferred_element_type=_f32,
                                   precision=_HI)
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(_f32)).astype(jnp.bfloat16)
    return (jax.lax.dot_general(hi, phi, nt, preferred_element_type=_f32)
            + jax.lax.dot_general(lo, phi, nt, preferred_element_type=_f32))


def _scale_and_bias(alpha, bias, n):
    """``(2, 2n + n*n)`` float32: each logit's ``alpha`` and its bias."""
    alpha = alpha.astype(_f32)
    scale = jnp.concatenate([jnp.full((n,), alpha[0]),
                             jnp.full((n,), alpha[1]),
                             jnp.full((n * n,), alpha[2])])
    return jnp.stack([scale, bias.astype(_f32)])


# ---------------------------------------------------------------------------
# The XLA tier (the declared fallback), the formula as written
# ---------------------------------------------------------------------------


def _sinkhorn(m, iters, eps):
    """``m (..., n, n)``: ``iters`` row- then column-normalisations."""
    def one(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return jax.lax.fori_loop(0, iters, one, m)


def _pack(h_pre, h_post, h_res):
    """``(R, n)``, ``(R, n)``, ``(R, n, n)`` -> ``mix (R, 128)``."""
    r, n = h_pre.shape
    mix = jnp.zeros((r, MIX_LANES), _f32)
    mix = mix.at[:, _PRE:_PRE + n].set(h_pre)
    mix = mix.at[:, _POST:_POST + n].set(h_post)
    cols = jnp.pad(jnp.swapaxes(h_res, 1, 2), ((0, 0), (0, 0), (0, 8 - n)))
    return mix.at[:, _RES:_RES + 8 * n].set(cols.reshape(r, 8 * n))


def unpack(mix, n):
    """``mix (R, 128)`` -> ``(H_pre (R, n), H_post (R, n), H_res (R, n,
    n))``."""
    r = mix.shape[0]
    cols = mix[:, _RES:_RES + 8 * n].reshape(r, n, 8)[:, :, :n]
    return (mix[:, _PRE:_PRE + n], mix[:, _POST:_POST + n],
            jnp.swapaxes(cols, 1, 2))


def _pre_xla(x, phi, alpha, bias, *, n, iters, eps, clamp):
    r = x.shape[0]
    c = x.shape[1] // n
    sb = _scale_and_bias(alpha, bias, n)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=1, keepdims=True)
                        + eps)
    lg = _product(x, phi) * inv * sb[0] + sb[1]
    h_pre = jax.nn.sigmoid(lg[:, :n])
    h_post = 2.0 * jax.nn.sigmoid(lg[:, n:2 * n])
    m = jnp.exp(jnp.clip(lg[:, 2 * n:].reshape(r, n, n), -clamp, clamp))
    h_res = _sinkhorn(m, iters, eps)
    h = jnp.einsum("ri,ric->rc", h_pre, x.reshape(r, n, c),
                   precision=_HI)
    return h, _pack(h_pre, h_post, h_res)


def _post_xla(x, y, mix, *, n):
    r = x.shape[0]
    c = x.shape[1] // n
    _, h_post, h_res = unpack(mix, n)
    out = jnp.einsum("rij,rjc->ric", h_res, x.reshape(r, n, c),
                     precision=_HI) + h_post[:, :, None] * y[:, None, :]
    return out.reshape(r, n * c)


# ---------------------------------------------------------------------------
# The Pallas tier
# ---------------------------------------------------------------------------


def _permutation(n, k):
    """``(128, k)``: the rows of the logits' transpose as the Sinkhorn
    tile lays them (:data:`MIX_LANES`); pad rows are zero."""
    out = jax.lax.broadcasted_iota(jnp.int32, (MIX_LANES, k), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (MIX_LANES, k), 1)
    src = jnp.full((MIX_LANES, k), -1, jnp.int32)
    # H_pre, H_post: logit i (and n + i) at row 0 + i (8 + i)
    src = jnp.where(out < n, out, src)
    src = jnp.where((out >= _POST) & (out < _POST + n), out - _POST + n, src)
    # M[i, j], logit 2n + n i + j, at row 16 + 8 j + i
    j, i = (out - _RES) // 8, (out - _RES) % 8
    res = (out >= _RES) & (out < _RES + 8 * n) & (i < n)
    src = jnp.where(res, 2 * n + n * i + j, src)
    return (src == col).astype(_f32)


def _pre_kernel(sb_ref, phi_ref, x_ref, h_ref, mix_ref, *, n, iters, eps,
                clamp):
    rows, width = x_ref.shape
    c = width // n
    k = phi_ref.shape[0]
    nt = (((1,), (1,)), ((), ()))
    ss = jnp.zeros((rows, 1), _f32)
    lg = jnp.zeros((rows, k), _f32)
    for i in range(n):
        xi = x_ref[:, i * c:(i + 1) * c]
        ss = ss + jnp.sum(xi * xi, axis=1, keepdims=True)
        lg = lg + _product(xi, phi_ref[:, i * c:(i + 1) * c])
    inv = jax.lax.rsqrt(ss / width + eps)
    lg = lg * inv * sb_ref[0:1, :] + sb_ref[1:2, :]           # (rows, k)
    # the tokens onto the lanes, each column of M a sublane tile
    t = jax.lax.dot_general(_permutation(n, k), lg, nt,
                            preferred_element_type=_f32, precision=_HI)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, rows), 0) < n
    h_pre = jax.nn.sigmoid(t[_PRE:_PRE + 8])
    h_post = 2.0 * jax.nn.sigmoid(t[_POST:_POST + 8])
    cols = [jnp.where(sub, jnp.exp(jnp.clip(
        t[_RES + 8 * j:_RES + 8 * (j + 1)], -clamp, clamp)), 0.0)
        for j in range(n)]

    def one(_, cols):
        rowsum = functools.reduce(jnp.add, cols)               # (8, rows)
        cols = [m / (rowsum + eps) for m in cols]
        return [m / (jnp.sum(m, axis=0, keepdims=True) + eps) for m in cols]
    cols = jax.lax.fori_loop(0, iters, lambda s, c: tuple(one(s, list(c))),
                             tuple(cols))
    zero = jnp.zeros((8, rows), _f32)
    tiles = [jnp.where(sub, h_pre, 0.0), jnp.where(sub, h_post, 0.0),
             *cols] + [zero] * ((MIX_LANES - _RES) // 8 - n)
    # and back to a row a token
    eye = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
           ).astype(_f32)
    mix = jax.lax.dot_general(eye, jnp.concatenate(tiles, axis=0), nt,
                              preferred_element_type=_f32, precision=_HI)
    mix_ref[...] = mix
    h = mix[:, _PRE:_PRE + 1] * x_ref[:, 0:c]
    for i in range(1, n):
        h = h + mix[:, _PRE + i:_PRE + i + 1] * x_ref[:, i * c:(i + 1) * c]
    h_ref[...] = h


# the mapping's eps is the architecture's (hc_eps), fixed for a model's
# life like its widths, not a schedule that moves between steps
# tpu-lint: disable=RETRACE-STATIC an architectural constant
@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "eps", "clamp", "interpret"))
def _pre_call(x, phi, sb, *, n, iters, eps, clamp, interpret):
    r, width = x.shape
    c = width // n
    k = phi.shape[0]
    rows = _PRE_ROWS
    grid = (r // rows,)
    fixed = lambda i: (0, 0)                                  # noqa: E731
    return pl.pallas_call(
        functools.partial(_pre_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp),
        grid=grid,
        in_specs=[pl.BlockSpec((2, k), fixed),
                  pl.BlockSpec((k, width), fixed),
                  pl.BlockSpec((rows, width), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, c), lambda i: (i, 0)),
                   pl.BlockSpec((rows, MIX_LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, c), _f32),
                   jax.ShapeDtypeStruct((r, MIX_LANES), _f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two tiles of rows in flight, Phi, a stream's pieces
            vmem_limit_bytes=max(32 << 20, 4 * rows * width * 4
                                 + 2 * k * width * phi.dtype.itemsize
                                 + 4 * rows * c * 4)),
        interpret=interpret,
        name="hyper_conn_pre",
    )(sb, phi, x)


def _post_kernel(mix_ref, x_ref, y_ref, o_ref, *, n):
    c = y_ref.shape[1]
    mix = mix_ref[...]
    y = y_ref[...]
    xs = [x_ref[:, j * c:(j + 1) * c] for j in range(n)]
    outs = []
    for i in range(n):
        acc = mix[:, _POST + i:_POST + i + 1] * y
        for j in range(n):
            lane = _RES + 8 * j + i
            acc = acc + mix[:, lane:lane + 1] * xs[j]
        outs.append(acc)
    for i in range(n):
        o_ref[:, i * c:(i + 1) * c] = outs[i]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _post_call(x, y, mix, *, n, interpret):
    r, width = x.shape
    c = width // n
    rows = _POST_ROWS
    at = lambda i: (i, 0)                                     # noqa: E731
    return pl.pallas_call(
        functools.partial(_post_kernel, n=n),
        grid=(r // rows,),
        in_specs=[pl.BlockSpec((rows, MIX_LANES), at),
                  pl.BlockSpec((rows, width), at),
                  pl.BlockSpec((rows, c), at)],
        out_specs=pl.BlockSpec((rows, width), at),
        out_shape=jax.ShapeDtypeStruct((r, width), _f32),
        # (mix, x, y): the streams go out where they came in
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, 6 * rows * width * 4)),
        interpret=interpret,
        name="hyper_conn_post",
    )(mix, x, y)


# ---------------------------------------------------------------------------
# Entries
# ---------------------------------------------------------------------------


def _rows_to(x, rows):
    pad = -x.shape[0] % rows
    return x if not pad else jnp.pad(x, ((0, pad), (0, 0)))


def hyper_conn_pre(x, phi, alpha, bias, *, n, iters, eps, clamp):
    """``x (R, n*C)`` float32 streams, ``phi (2n + n*n, n*C)``, ``alpha
    (3,)``, ``bias (2n + n*n,)`` -> ``(h (R, C), mix (R, 128))``, both
    float32 (module docstring)."""
    _check(n)
    mode = kernel_mode(x, n)
    if mode is None:
        return _pre_xla(x, phi, alpha, bias, n=n, iters=iters, eps=eps,
                        clamp=clamp)
    r = x.shape[0]
    h, mix = _pre_call(_rows_to(x, _PRE_ROWS), phi,
                       _scale_and_bias(alpha, bias, n), n=n, iters=iters,
                       eps=float(eps), clamp=float(clamp),
                       interpret=mode == "interpret")
    return h[:r], mix[:r]


def hyper_conn_post(x, y, mix, *, n):
    """``x (R, n*C)`` float32 streams, ``y (R, C)`` what the sublayer
    gave, ``mix`` from :func:`hyper_conn_pre` -> ``X' (R, n*C)``."""
    _check(n)
    mode = kernel_mode(x, n)
    if mode is None:
        return _post_xla(x, y.astype(_f32), mix, n=n)
    r = x.shape[0]
    out = _post_call(_rows_to(x, _POST_ROWS),
                     _rows_to(y.astype(_f32), _POST_ROWS),
                     _rows_to(mix, _POST_ROWS), n=n,
                     interpret=mode == "interpret")
    return out[:r]


def kernel_mode(x, n):
    """The rule: the mode the kernels run in, or ``None`` for the XLA
    tier.  Taken wherever its tiles fit: float32 streams whose width is
    whole lane rows a stream.  The XLA tier is the same formula as a
    chain of small operations a sublayer, each a pass over the rows; on
    a v5e it made the streams cell's decode step 0.12 ms (0.56%) longer
    than the kernels do (PERF.md section 6)."""
    width = x.shape[1]
    return choose("hyper_connection", fits=(
        x.dtype == _f32 and width % n == 0 and (width // n) % 128 == 0))


def _audit_programs():
    sds = jax.ShapeDtypeStruct
    n, c = 4, 128
    ex = (sds((8, n * c), _f32), sds((2 * n + n * n, n * c), jnp.bfloat16),
          sds((3,), jnp.bfloat16), sds((2 * n + n * n,), jnp.bfloat16))
    kw = dict(n=n, iters=20, eps=1e-6, clamp=30.0)

    def _pallas(x, phi, alpha, bias):
        return _pre_call(_rows_to(x, _PRE_ROWS), phi,
                         _scale_and_bias(alpha, bias, n), interpret=False,
                         **kw)

    def _xla(x, phi, alpha, bias):
        return _pre_xla(x, phi, alpha, bias, **kw)

    return [("pallas", _pallas, ex), ("xla", _xla, ex)]


register_kernel(
    "hyper_connection",
    xla_fallback="apex_tpu.kernels.hyper_connection._pre_xla",
    doc="The mapping of a manifold-constrained hyper-connection: pre "
        "(norm, product, Sinkhorn, h) and post (mix of the streams)",
    audit_programs=_audit_programs)
