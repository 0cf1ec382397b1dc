"""The mHC kernels (``apex_tpu/kernels/hyper_connection.py``) against the
formula written out here, on the CPU: the XLA tier and the Pallas tier
(interpreted), the doubly stochastic mixing matrix, and a tolerance tight
enough that a mapping of fewer Sinkhorn iterations or streams kept in
bfloat16 fails it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.kernels import hyper_connection as hc
from apex_tpu.kernels.dispatch import force_mode

N, C, ROWS, EPS, CLAMP = 4, 256, 72, 1e-6, 30.0
K = 2 * N + N * N

# float32 against float32 through the same arithmetic in another order:
# the product with a bfloat16 Phi in two bfloat16 pieces of X (2^-17 of
# X, 1e-5 on a logit spread 2.4) against the float32 one, sums down a
# tile against sums along an axis.  The widest difference is 2.5e-5 (X'
# of the bfloat16 Phi, either tier); ten iterations read 0.21 and
# bfloat16 streams 0.033 at the draw below
TOL = 5e-5


def _draw(spread, seed=0, phi_dtype=jnp.bfloat16):
    """Streams, the sublayer's output and a mapping whose logits spread
    about ``spread`` (the published widths' draw spreads 2.4)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (ROWS, N * C), jnp.float32)
    y = jax.random.normal(ks[1], (ROWS, C), jnp.float32)
    phi = (spread / np.sqrt(N * C)
           * jax.random.normal(ks[2], (K, N * C))).astype(phi_dtype)
    alpha = (1.0 + 0.02 * jax.random.normal(ks[3], (3,))).astype(phi_dtype)
    bias = (0.02 * jax.random.normal(ks[4], (K,))).astype(phi_dtype)
    return x, y, phi, alpha, bias


def _formula(x, y, phi, alpha, bias, iters=20):
    """The mapping and the mix as the equations state them, float32 at
    ``highest``."""
    xs = x.reshape(ROWS, N, C)
    r = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
    lg = jnp.dot(r, phi.astype(jnp.float32).T, precision="highest")
    a, b = alpha.astype(jnp.float32), bias.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(a[0] * lg[:, :N] + b[:N])
    h_post = 2 * jax.nn.sigmoid(a[1] * lg[:, N:2 * N] + b[N:2 * N])
    m = jnp.exp(jnp.clip(a[2] * lg[:, 2 * N:].reshape(ROWS, N, N)
                         + b[2 * N:].reshape(N, N), -CLAMP, CLAMP))
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + EPS)
        m = m / (m.sum(-2, keepdims=True) + EPS)
    h = jnp.einsum("ri,ric->rc", h_pre, xs, precision="highest")
    out = jnp.einsum("rij,rjc->ric", m, xs, precision="highest") \
        + h_post[:, :, None] * y[:, None, :]
    return h, h_pre, h_post, m, out.reshape(ROWS, N * C)


def _kernels(tier, x, y, phi, alpha, bias, iters=20):
    mode = {"xla": "off", "pallas_interpret": "interpret"}[tier]
    with force_mode(mode):
        h, mix = hc.hyper_conn_pre(x, phi, alpha, bias, n=N, iters=iters,
                                   eps=EPS, clamp=CLAMP)
        out = hc.hyper_conn_post(x, y, mix, n=N)
    return (h, *hc.unpack(mix, N), out)


def _close(got, want):
    return float(jnp.max(jnp.abs(got - want))) <= TOL


@pytest.mark.parametrize("phi_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("tier", ["xla", "pallas_interpret"])
def test_both_tiers_are_the_formula(tier, phi_dtype):
    args = _draw(2.4, phi_dtype=jnp.dtype(phi_dtype))
    want = _formula(*args)
    got = _kernels(tier, *args)
    for name, g, w in zip(("h", "H_pre", "H_post", "H_res", "X'"), got,
                          want):
        assert g.shape == w.shape, name
        assert _close(g, w), (name, float(jnp.max(jnp.abs(g - w))))


def test_h_res_is_doubly_stochastic():
    """Rows and columns of ``H_res`` sum to 1 at a mapping whose logits
    spread half a unit (the tiny model's 0.45): 20 iterations have
    converged there (the columns, normalised last, to a rounding; the
    rows to 9e-6 already at a spread of one unit)."""
    _, _, _, h_res, _ = _kernels("pallas_interpret", *_draw(0.5))
    assert float(jnp.max(jnp.abs(h_res.sum(-1) - 1))) < 1e-5
    assert float(jnp.max(jnp.abs(h_res.sum(-2) - 1))) < 1e-5
    assert float(jnp.min(h_res)) > 0


def test_the_tolerance_sees_fewer_iterations_and_bfloat16_streams():
    """Where the logits span several units the iterations have not
    converged at 10, and the streams' bfloat16 rounding is wider than
    the tolerance: both fail what the kernels pass."""
    args = _draw(4.0, seed=1)
    want = _formula(*args)
    assert _close(_kernels("xla", *args)[3], want[3])
    ten = _kernels("xla", *args, iters=10)
    assert not _close(ten[3], want[3])
    assert not _close(ten[4], want[4])
    x, y, *rest = args
    rounded = x.astype(jnp.bfloat16).astype(jnp.float32)
    out = _kernels("xla", rounded, y, *rest)[4]
    assert not _close(out, want[4])


def test_the_mix_layout_is_the_same_for_both_tiers():
    """A row of ``mix`` lays ``H_res[i, j]`` at ``16 + 8 j + i``: the post
    of either tier reads the pre of either."""
    x, y, phi, alpha, bias = args = _draw(2.4)
    mixes = {}
    for mode in ("off", "interpret"):
        with force_mode(mode):
            _, mixes[mode] = hc.hyper_conn_pre(
                x, phi, alpha, bias, n=N, iters=20, eps=EPS, clamp=CLAMP)
    mix_xla = mixes["off"]
    with force_mode("interpret"):
        out = hc.hyper_conn_post(x, y, mix_xla, n=N)
    assert _close(mixes["interpret"], mix_xla)
    assert float(jnp.max(jnp.abs(mix_xla[:, 16 + 8 * N:]))) == 0.0
    assert hc.mix_lane("res", 2, 1) == 16 + 8 + 2
    assert _close(out, _formula(*args)[4])
