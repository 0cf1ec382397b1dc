"""The recurrence of a state-space layer (``kernels/ssm.py``) and the
grouped matmul's whole-width block (``kernels/grouped_matmul.py``): each
Pallas tier in ``interpret`` mode against its XLA tier, the chunked form
against the recurrence one position after another, and the tile rule at
the widths the benchmark's configurations run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.kernels import grouped_matmul as gm
from apex_tpu.kernels import ssm
from apex_tpu.kernels.dispatch import catalog, force_mode

pytestmark = pytest.mark.kernels

HEADS, P, GROUPS, N = 4, 64, 2, 16


def _inputs(seed, q):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (q, HEADS, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (q, HEADS)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (HEADS,), minval=0.0, maxval=2.7))
    b = jax.random.normal(k[3], (q, GROUPS, N))
    c = jax.random.normal(k[4], (q, GROUPS, N))
    h0 = jax.random.normal(k[5], (N, HEADS * P))
    return x, dt, a, b, c, h0


# -- the chunked form against the recurrence as written --------------------------


def _scan_steps(x, dt, a, b, c, h0):
    """The recurrence as written, one position after another (operands
    as ``ssm.ssm_chunk_scan``'s): ``H_t = a_t H_{t-1} + dt_t x_t (x)
    B_t``, ``y_t = H_t C_t``, all float32."""
    q, heads, p = x.shape
    groups, n = b.shape[1:]
    r = heads // groups

    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        decay = jnp.exp(dt_t * a).reshape(groups, r)
        h = h * decay[None, :, :, None] + jnp.einsum(
            "gn,grp->ngrp", b_t, (x_t * dt_t[:, None]).reshape(groups, r, p),
            precision=jax.lax.Precision.HIGHEST)
        return h, jnp.einsum("gn,ngrp->grp", c_t, h, precision=jax.lax.Precision.HIGHEST)
    h, y = jax.lax.scan(step, h0.reshape(n, groups, r, p), (x, dt, b, c))
    return y.reshape(q, heads, p), h.reshape(n, heads * p)


@pytest.mark.parametrize("q,chunk", [(37, 8), (64, 16), (5, 8), (128, 128)])
def test_chunked_scan_is_the_stepwise_recurrence(q, chunk):
    """Lengths that are and are not multiples of the chunk, from a state
    that is not zero.  float32 against float32: the chunked form sums a
    chunk's decay in one cumulative sum and exponentiates differences of
    it where the recurrence multiplies step by step, and contracts 16
    state columns in another order: 1e-5 of an output of size ~1 and 2e-5
    of a larger one (they reach ~30 at 128 positions)."""
    x, dt, a, b, c, h0 = _inputs(q, q)
    y_steps, h_steps = _scan_steps(x, dt, a, b, c, h0)
    y, h = ssm.ssm_chunk_scan(x, dt, a, b, c, h0, chunk=chunk)
    assert float(jnp.abs(y_steps).max()) > 1.0
    np.testing.assert_allclose(y, y_steps, atol=1e-5, rtol=2e-5)
    np.testing.assert_allclose(h, h_steps, atol=1e-5, rtol=2e-5)


def test_a_row_whose_dt_is_nought_leaves_the_state_alone():
    """A chunk's padded tail: rows past the real ones carry ``dt = 0``
    (whatever their other inputs) and the state after the chunk is the
    state after its real rows."""
    x, dt, a, b, c, h0 = _inputs(3, 24)
    real = 13
    dt = dt.at[real:].set(0.0)
    _, h = ssm.ssm_chunk_scan(x, dt, a, b, c, h0, chunk=8)
    _, want = _scan_steps(x[:real], dt[:real], a, b[:real], c[:real],
                                 h0)
    np.testing.assert_allclose(h, want, atol=1e-5)


# -- the decode step: both tiers --------------------------------------------------


def _step_operands(seed, batch):
    x, dt, a, b, c, _ = _inputs(seed, batch)
    decay = jnp.repeat(jnp.exp(dt * a), P, axis=1)
    dtx = jnp.repeat(dt, P, axis=1) * x.reshape(batch, -1)
    return decay, dtx, b, c


@pytest.mark.parametrize("tier", ["xla", "pallas_interpret"])
def test_state_update_steps_each_slot_where_it_lies(tier):
    """Slots out of order, two padding rows on the null slot, heads of
    both groups, the second of two layers: each live slot's state is
    what one step of the recurrence gives, ``y`` is ``H C``, and no other
    row of the buffer (the other layer, the slots no session of the batch
    holds) is touched."""
    slots_n, null = 6, 6
    rng = np.random.default_rng(5)
    state = jnp.asarray(rng.standard_normal((2, slots_n + 1, N, HEADS * P)),
                        jnp.float32)
    slots = jnp.asarray([4, 0, null, 2, null], jnp.int32)
    decay, dtx, b, c = _step_operands(9, 5)
    with force_mode("interpret" if tier == "pallas_interpret" else "off"):
        assert (ssm.kernel_mode(state, b) is None) == (tier == "xla")
        y, after = ssm.ssm_state_update(state, 1, slots, decay, dtx, b, c)
    gw = HEADS * P // GROUPS
    for row, slot in enumerate([4, 0, null, 2]):
        if slot == null:
            continue
        bcol = np.repeat(np.asarray(b[row]).T, gw, axis=1)   # (N, heads*P)
        ccol = np.repeat(np.asarray(c[row]).T, gw, axis=1)
        want = np.asarray(state[1, slot]) * np.asarray(decay[row])[None] \
            + bcol * np.asarray(dtx[row])[None]
        np.testing.assert_allclose(after[1, slot], want, atol=1e-6)
        np.testing.assert_allclose(y[row], (want * ccol).sum(0), atol=1e-5)
    np.testing.assert_array_equal(after[0], state[0])
    for slot in (1, 3, 5):
        np.testing.assert_array_equal(after[1, slot], state[1, slot])


def test_state_update_tiers_agree_over_many_steps():
    """Twenty steps through both tiers from the same buffer: the same
    states and outputs (float32, the same operations in another order)."""
    state = jnp.zeros((1, 4, N, HEADS * P), jnp.float32)
    slots = jnp.asarray([2, 0, 1], jnp.int32)
    out = {}
    for mode in ("off", "interpret"):
        with force_mode(mode):
            st, ys = state, []
            for t in range(20):
                y, st = ssm.ssm_state_update(st, 0, slots,
                                             *_step_operands(100 + t, 3))
                ys.append(y)
        out[mode] = (jnp.stack(ys), st)
    np.testing.assert_allclose(out["interpret"][0], out["off"][0], atol=2e-5)
    np.testing.assert_allclose(out["interpret"][1], out["off"][1], atol=2e-5)
    assert float(jnp.abs(out["off"][1][0, 3]).max()) == 0.0


def test_the_rule_and_the_registration():
    """The kernel takes a float32 state whose groups are whole lane rows
    wide; anything else, and every backend without a kernel mode, is the
    XLA tier's.  It is registered with its declared fallback."""
    b = jnp.zeros((1, GROUPS, N))
    wide = jnp.zeros((1, 2, N, HEADS * P))
    narrow = jnp.zeros((1, 2, N, GROUPS * 64))
    assert ssm.kernel_mode(wide, b) is None            # the CPU: no mode
    with force_mode("interpret"):
        assert ssm.kernel_mode(wide, b) == "interpret"
        assert ssm.kernel_mode(narrow, b) is None
        assert ssm.kernel_mode(wide.astype(jnp.bfloat16), b) is None
    spec = catalog()["ssm_state_update"]
    assert spec.xla_fallback == "apex_tpu.kernels.ssm._update_xla"
    assert [t for t, _, _ in spec.audit_programs()] == ["pallas", "xla"]


# -- the grouped matmul: a width no lane-row tile divides -------------------------


@pytest.mark.parametrize("n,tile", [
    (1856, 1856),       # 14.5 lane rows: the whole width, one block
    (2688, 896), (3712, 128),
    # the two older families' widths keep their tiles
    (7168, 1024), (4096, 1024), (2048, 1024), (2304, 768), (1792, 896),
    (896, 896),
    (4104, None),       # too wide to take whole
    (1020, None),       # no whole sublane tiles
])
def test_tile_rule(n, tile):
    assert gm._tile_of(n) == tile


@pytest.mark.parametrize("k,n,transposed", [
    (2688, 1856, True),     # an ungated expert's input matrix, (out, in)
    (1856, 2688, False),    # its output matrix
    (2688, 1856, False),
    (2304, 1792, False),    # the window-and-full family's gate | up
    (896, 2304, False),
    (7168, 4096, False),    # the latent family's gate | up
])
def test_grouped_matmul_tiers_agree_at_the_benchmarks_widths(k, n,
                                                             transposed):
    """bfloat16 operands, float32 accumulation in both tiers: the same
    products summed in another order (``K`` tiles against one
    contraction), 3 experts of which one gets no row."""
    rng = np.random.default_rng(k + n)
    group = jnp.asarray([2, 0, 0, 3, 2, 0, 3, 3, 0, 2], jnp.int32)
    lay = gm.tile_layout(group, 3, 10, gm.TILE_ROWS)
    lhs = jnp.asarray(rng.standard_normal((lay.pair_of_row.shape[0], k)),
                      jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((3, k, n)) / np.sqrt(k),
                      jnp.bfloat16)
    given = jnp.swapaxes(rhs, 1, 2) if transposed else rhs
    with force_mode("interpret"):
        assert gm.kernel_mode(lhs, given, lay.tile, transposed) == \
            "interpret"
        got = gm.grouped_matmul(lhs, given, lay, transposed)
    with force_mode("off"):
        want = gm.grouped_matmul(lhs, given, lay, transposed)
    rows = np.asarray(lay.pair_of_row) >= 0
    assert rows.sum() == 7       # group 1 gets no row; 3 is held elsewhere
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[rows], np.asarray(want, np.float32)[rows],
        atol=0.04, rtol=0.02)
