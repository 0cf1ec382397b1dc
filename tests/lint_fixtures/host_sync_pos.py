"""HOST-SYNC positive: device round-trips inside jitted code."""
import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def bad_norm_step(params, grads):
    # BAD: .item() blocks on a device fetch every step
    gnorm = jnp.sqrt(sum((g * g).sum() for g in grads)).item()
    return [p - 0.1 * g / gnorm for p, g in zip(params, grads)]


def bad_overflow_step(params, grads, flag):
    # BAD: Python branching on a traced value
    if flag:
        return params
    return [p - 0.1 * g for p, g in zip(params, grads)]


def bad_fetch_step(state, batch):
    # BAD: np.asarray of a traced value materializes on host
    host = np.asarray(batch)
    # BAD: device_get inside the compiled step
    stats = jax.device_get(state)
    return state, host, stats


def bad_scale_step(params, scale):
    # BAD: float() of a traced scalar is a host sync
    s = float(scale)
    return [p * s for p in params]


train = jax.jit(bad_overflow_step)
fetch = jax.jit(bad_fetch_step)
scaled = jax.jit(bad_scale_step)


def _choose(x):
    # BAD (interprocedural): x arrives traced from the jitted caller —
    # the branch is a device fetch even though this helper never
    # mentions jax
    if x > 0:
        return x
    return -x


@jax.jit
def routed_step(v):
    return _choose(v * 2.0)
