"""The trailing-window daily refresh: its days from the seed and a plain
``jax.numpy`` reference of the per-cohort estimand over the days a
store holds (the ``criteo_uplift_daily28`` configuration).

A day is ``uplift.make_panel``'s law with ``day_rows`` rows, drawn from
its own key.  Days arrive in order and are whole, so row ``i`` of day
``d`` has the global arrival index ``g = d * day_rows + i``.  The fold
of a row is the documented rule of the moment store,

    fold(g) = randint(fold_in(column_key, g), K)

re-derived here (``row_folds``) and not imported from the program.

The estimand over a set of rows is ``uplift.segmented_dml``'s: for each
(cohort, fold) cell a ridge model of the visit and a fixed-majorizer MM
logistic model of the treatment (``iters`` steps from zero), both on
the cohort's other folds; the orthogonal final stage and its HC0
standard error from the out-of-fold residuals.  Only the folds differ:
they come from the rule above, not from one balanced permutation.
Every data-sized sum is a one-hot Gram over row chunks, float32 at
``HIGHEST``; with ``lowp=True`` (the control) every factor of a
data-sized product is first rounded to bfloat16.  X comes lane-major,
(p, n), so that 28 days of it take 0.9 GB of the chip, not the 7 GB an
(n, 12) array takes with its rows padded to 128 lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.refs.estimands import F32, highest, rnd
from chipbench.refs.uplift import _onehot_sum, _outer, make_panel


def make_day(key, config: dict) -> dict:
    """One day: {"X" (day_rows, p), "y", "t", "sids"} from ``key``."""
    d = make_panel(key, dict(config, n=config["day_rows"]))
    return {k: d[k] for k in ("X", "y", "t", "sids")}


@functools.partial(jax.jit, static_argnames=("n", "k"))
def row_folds(column_key, start, *, n: int, k: int):
    """Folds of the global rows [start, start + n): one key per row,
    ``fold_in(column_key, g)``, and one draw of ``randint(., 0, k)``."""
    g = jnp.asarray(start, jnp.uint32) + jnp.arange(n, dtype=jnp.uint32)
    keys = jax.vmap(lambda i: jax.random.fold_in(column_key, i))(g)
    return jax.vmap(lambda kk: jax.random.randint(kk, (), 0, k))(keys).astype(
        jnp.int32)


def _chunked(fn, Xt, rows, pad_values, chunks, init):
    """Left fold of ``fn(acc, x, *row_chunks)`` over ``chunks`` chunks;
    ``x`` is the chunk of the lane-major ``Xt`` turned to (m, p)."""
    n = Xt.shape[1]
    m = -(-n // chunks)
    pad = m * chunks - n
    Xt = jnp.pad(Xt, ((0, 0), (0, pad)))
    rows = [jnp.pad(a, (0, pad), constant_values=v)
            for a, v in zip(rows, pad_values)]

    def step(acc, c):
        x = jax.lax.dynamic_slice_in_dim(Xt, c * m, m, axis=1).T
        return fn(acc, x, *[jax.lax.dynamic_slice_in_dim(a, c * m, m)
                            for a in rows]), None

    return jax.lax.scan(step, init, jnp.arange(chunks))[0]


@highest
@functools.partial(jax.jit,
                   static_argnames=("E", "k", "iters", "chunks", "lowp"))
def window_dml(Xt, y, t, sids, folds, *, E: int, k: int, lam: float,
               iters: int, chunks: int, lowp: bool):
    """Per-cohort DML over the rows given, each row in its ``folds``
    fold: ridge visit and fixed-majorizer MM logistic treatment models
    per (cohort, fold complement), per-cohort final stage and HC0 SE.
    ``Xt`` is (p, n).  Returns (theta (E,), se (E,), beta_y (E, k, q),
    beta_t (E, k, q)), q = p + 1, intercept last."""
    q, n = Xt.shape[0] + 1, Xt.shape[1]
    rows = [y, t, sids, folds]
    pads = [0, 0, -1, 0]  # a padded row's cohort -1 one-hots to zero

    def aug(x):
        return jnp.concatenate([x, jnp.ones((x.shape[0], 1), F32)], axis=1)

    def gram(acc, x, yc, tc, s, f):
        d = jnp.concatenate([aug(x), yc[:, None]], axis=1)
        return acc + _onehot_sum(s * k + f, E * k, _outer(d, d, lowp))

    Gh = _chunked(gram, Xt, rows, pads, chunks,
                  jnp.zeros((E * k, (q + 1) ** 2), F32))
    Gh = Gh.reshape(E, k, q + 1, q + 1)
    cnt = jax.ops.segment_sum(jnp.ones((n,), F32), sids * k + folds,
                              E * k).reshape(E, k)
    Gc = Gh.sum(1, keepdims=True) - Gh
    ne = jnp.maximum(cnt.sum(1, keepdims=True) - cnt, 1.0)
    eye = jnp.eye(q, dtype=F32)
    A = Gc[..., :q, :q] / ne[..., None, None] + lam * eye
    beta_y = jnp.linalg.solve(A, (Gc[..., :q, q] / ne[..., None])[..., None])[..., 0]
    H0 = Gc[..., :q, :q] / (4.0 * ne[..., None, None]) + lam * eye

    def mm(_, beta):
        def grad(acc, x, yc, tc, s, f):
            t1, t2 = acc
            xa = aug(x)
            logit = jnp.einsum("np,nkp->nk", rnd(xa, lowp), rnd(beta[s], lowp))
            r = jax.nn.sigmoid(logit) - tc[:, None]  # (m, k)
            rr = jnp.take_along_axis(r, f[:, None], axis=1)  # own fold
            return (t1 + _onehot_sum(s, E, _outer(r, xa, lowp)),
                    t2 + _onehot_sum(s * k + f, E * k, _outer(rr, xa, lowp)))

        t1, t2 = _chunked(grad, Xt, rows, pads, chunks,
                          (jnp.zeros((E, k * q), F32), jnp.zeros((E * k, q), F32)))
        g = (t1.reshape(E, k, q) - t2.reshape(E, k, q)) / ne[..., None] + lam * beta
        return beta - jnp.linalg.solve(H0, g[..., None])[..., 0]

    beta_t = jax.lax.fori_loop(0, iters, mm, jnp.zeros((E, k, q), F32))

    def residuals(x, yc, tc, s, f):
        """(rt, ry) of a chunk: out-of-fold predictions by each row's
        own (cohort, fold) models."""
        xa = rnd(aug(x), lowp)
        my = jnp.sum(xa * rnd(beta_y[s, f], lowp), axis=1)
        mt = jax.nn.sigmoid(jnp.sum(xa * rnd(beta_t[s, f], lowp), axis=1))
        return tc - mt, yc - my

    def final(acc, *chunk):
        m = jnp.stack(residuals(*chunk), axis=1)
        return acc + _onehot_sum(chunk[3], E, _outer(m, m, lowp))

    g = _chunked(final, Xt, rows, pads, chunks, jnp.zeros((E, 4), F32))
    nseg = jnp.maximum(jax.ops.segment_sum(jnp.ones((n,), F32), sids, E), 1.0)
    a = g[:, 0] + 1e-8 * nseg
    theta = g[:, 1] / a

    def meat(acc, *chunk):
        rt, ry = residuals(*chunk)
        s = chunk[3]
        me = ((ry - theta[s] * rt) * rt)[:, None]
        return acc + _onehot_sum(s, E, _outer(me, me, lowp))

    mt = _chunked(meat, Xt, rows, pads, chunks, jnp.zeros((E, 1), F32))[:, 0]
    return theta, jnp.sqrt(mt) / a, beta_y, beta_t
