"""Synthetic causal data, made on the device in one jitted call per seed.

Copied from ``repro.data.causal_dgp.make_causal_data`` (the partially
linear DGP of the paper's section 5.3, after dowhy's ``linear_dataset``)
so that a later change to the program cannot move the data:

    X ~ N(0, I_p)
    T ~ Bernoulli(sigmoid(<a, X>))                 (binary)
      = <a, X> + N(0, 1)                           (continuous)
    theta(x) = effect                              (homogeneous)
             = effect * (1 + 0.5 * x_0)            (heterogeneous)
    Y = theta(X) * T + <b, X> + N(0, 1)

with the first ``min(p, 10)`` covariates driving T and Y.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """The run's root key; any whole number, 64-bit seeds included."""
    return jax.random.PRNGKey(int(seed))


def _make(key, n, p, discrete, heterogeneous):
    kx, ka, kb, kt, ke = jax.random.split(key, 5)
    X = jax.random.normal(kx, (n, p), jnp.float32)
    live = min(p, 10)
    a = jnp.zeros((p,), jnp.float32).at[:live].set(
        jax.random.normal(ka, (live,), jnp.float32) / jnp.sqrt(live))
    b = jnp.zeros((p,), jnp.float32).at[:live].set(
        jax.random.normal(kb, (live,), jnp.float32))
    logits = jnp.matmul(X, a, precision="highest")
    if discrete:
        t = jax.random.bernoulli(kt, jax.nn.sigmoid(logits)).astype(jnp.float32)
    else:
        t = logits + jax.random.normal(kt, (n,), jnp.float32)
    cate = 1.0 + 0.5 * X[:, 0] if heterogeneous else jnp.ones((n,), jnp.float32)
    y = cate * t + jnp.matmul(X, b, precision="highest") + jax.random.normal(
        ke, (n,), jnp.float32)
    return {"X": X, "t": t, "y": y}


@functools.lru_cache(maxsize=None)
def _jitted(n, p, discrete, heterogeneous):
    return jax.jit(functools.partial(
        _make, n=n, p=p, discrete=discrete, heterogeneous=heterogeneous))


def make_data(key, n: int, p: int, *, discrete: bool,
              heterogeneous: bool) -> dict:
    """{"X" (n, p), "t" (n,), "y" (n,)} float32, made in one jitted call
    from ``key``."""
    return _jitted(int(n), int(p), bool(discrete), bool(heterogeneous))(key)
