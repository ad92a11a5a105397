"""The binary uplift panel: its data from the seed and a plain
``jax.numpy`` reference of the per-cohort estimand the sweep computes.

Adapted from ``chip_smoke.py`` (``_ref_segmented_dml``).  The data
(a Criteo-shaped randomised campaign log, the ``criteo_uplift_e64``
configuration):

    cohort ~ Zipf(s) over E ids, drawn per row   (rows in log order)
    X ~ N(0, I_p)
    t ~ Bernoulli(treated share)                 (randomised)
    visit ~ Bernoulli(min(base * exp(<b, X> - |b|^2 / 2) + u_cohort * t, 1))

with ``u_e`` each cohort's uplift, drawn around one percentage point,
and ``base`` set so that the visit rate is the configuration's overall.

The estimand, per cohort, is cross-fit DML on one fold assignment
shared by every cohort: for each (cohort, fold) cell a ridge model of
the visit and a logistic model of the treatment, both fitted on the
cohort's other folds; the logistic model is the Boehning-Lindsay
fixed-majorizer iteration (H0 = Gram / 4 + lam I) run for a fixed
``iters`` steps from zero, which is what the program defines (not
Newton to convergence); then the orthogonal final stage and its HC0
standard error from the out-of-fold residuals.  Every data-sized sum is
a one-hot Gram over row chunks, float32 at ``HIGHEST``; with
``lowp=True`` (the control) every factor of a data-sized product is
first rounded to bfloat16, what one bfloat16 MXU pass computes.  The
small solves stay float32 in both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.refs.estimands import F32, HI, fold_ids, highest, rnd


def zipf_shares(E: int, s: float):
    """(E,) cohort shares, proportional to (e + 1) ** -s."""
    w = jnp.arange(1, E + 1, dtype=F32) ** -s
    return w / w.sum()


@functools.partial(jax.jit, static_argnames=("n", "p", "E"))
def _make(key, *, n, p, E, zipf_s, treated, visit_rate, uplift, uplift_sd,
          b_norm):
    kc, kx, kt, kv, ku, kb = jax.random.split(key, 6)
    shares = zipf_shares(E, zipf_s)
    cdf = jnp.cumsum(shares)
    u = jax.random.uniform(kc, (n,), F32)
    sids = jnp.minimum(jnp.searchsorted(cdf, u, side="right"), E - 1)
    X = jax.random.normal(kx, (n, p), F32)
    t = jax.random.bernoulli(kt, treated, (n,)).astype(F32)
    up = jnp.clip(uplift + uplift_sd * jax.random.normal(ku, (E,), F32),
                  0.0, 3.0 * uplift)
    base = visit_rate - treated * jnp.sum(shares * up)
    b = jax.random.normal(kb, (p,), F32)
    b = b_norm * b / jnp.linalg.norm(b)
    lift = jnp.exp(jnp.matmul(X, b, precision=HI) - 0.5 * b_norm ** 2)
    prob = jnp.minimum(base * lift + up[sids] * t, 1.0)
    y = jax.random.bernoulli(kv, prob).astype(F32)
    return {"X": X, "y": y, "t": t, "sids": sids.astype(jnp.int32),
            "uplift": up}


def make_panel(key, config: dict) -> dict:
    """{"X" (n, p), "y" (n,) visit, "t" (n,), "sids" (n,) int32,
    "uplift" (E,)} from ``key`` and the configuration, in one jitted
    call on the device."""
    d = config["data"]
    return _make(key, n=int(config["n"]), p=int(config["p"]),
                 E=int(config["segments"]), zipf_s=d["zipf_s"],
                 treated=d["treated_share"], visit_rate=d["visit_rate"],
                 uplift=d["uplift_mean"], uplift_sd=d["uplift_sd"],
                 b_norm=d["visit_feature_norm"])


def _chunked(fn, arrays, pad_values, chunks, init):
    """Left fold of ``fn(acc, *chunk)`` over ``chunks`` row chunks; the
    rows are padded with ``pad_values`` to a whole number of chunks."""
    n = arrays[0].shape[0]
    m = -(-n // chunks)
    pad = m * chunks - n
    arrays = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                      constant_values=v) for a, v in zip(arrays, pad_values)]

    def step(acc, c):
        return fn(acc, *[jax.lax.dynamic_slice_in_dim(a, c * m, m)
                         for a in arrays]), None

    return jax.lax.scan(step, init, jnp.arange(chunks))[0]


def _onehot_sum(ids, n_seg, outer):
    """sum_{ids_n = s} outer_n -> (n_seg, d): a one-hot matmul (the
    one-hot is 0/1, exact at any precision)."""
    return jnp.matmul(jax.nn.one_hot(ids, n_seg, dtype=F32).T, outer,
                      precision=HI)


def _outer(u, v, lowp):
    """Row-wise u_n (x) v_n, flattened, from float32 (or bfloat16-rounded)
    factors."""
    o = rnd(u, lowp)[:, :, None] * rnd(v, lowp)[:, None, :]
    return o.reshape(u.shape[0], -1)


@highest
@functools.partial(jax.jit,
                   static_argnames=("E", "k", "iters", "chunks", "lowp"))
def segmented_dml(X, y, t, sids, key, *, E: int, k: int, lam: float,
                  iters: int, chunks: int, lowp: bool):
    """Per-cohort DML with one shared fold assignment (``fold_ids(key)``,
    the program's): ridge visit and fixed-majorizer MM logistic
    treatment models per (cohort, fold complement), per-cohort final
    stage and HC0 SE.  Every pass over the rows runs in ``chunks`` row
    chunks, so no data-sized array but the inputs is ever whole.
    Returns (theta (E,), se (E,), beta_y (E, k, q), beta_t (E, k, q)),
    q = p + 1, intercept last."""
    n, q = X.shape[0], X.shape[1] + 1
    folds = fold_ids(key, n, k)
    rows = [X, y, t, sids, folds]
    pads = [0, 0, 0, -1, 0]  # a padded row's cohort -1 one-hots to zero

    def aug(x):
        return jnp.concatenate([x, jnp.ones((x.shape[0], 1), F32)], axis=1)

    def gram(acc, x, yc, tc, s, f):
        d = jnp.concatenate([aug(x), yc[:, None]], axis=1)
        return acc + _onehot_sum(s * k + f, E * k, _outer(d, d, lowp))

    Gh = _chunked(gram, rows, pads, chunks,
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

        t1, t2 = _chunked(grad, rows, pads, chunks,
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

    g = _chunked(final, rows, pads, chunks, jnp.zeros((E, 4), F32))
    nseg = jnp.maximum(jax.ops.segment_sum(jnp.ones((n,), F32), sids, E), 1.0)
    a = g[:, 0] + 1e-8 * nseg
    theta = g[:, 1] / a

    def meat(acc, *chunk):
        rt, ry = residuals(*chunk)
        s = chunk[3]
        me = ((ry - theta[s] * rt) * rt)[:, None]
        return acc + _onehot_sum(s, E, _outer(me, me, lowp))

    mt = _chunked(meat, rows, pads, chunks, jnp.zeros((E, 1), F32))[:, 0]
    return theta, jnp.sqrt(mt) / a, beta_y, beta_t
