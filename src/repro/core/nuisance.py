"""Nuisance model zoo for Double-ML (m_y = E[Y|X], m_t = E[T|X]).

Every model is a triple of pure functions (init / fit / predict) with a
*sample-weight* argument, which is the key to the paper's C1 translation:
the K out-of-fold fits become ONE batched program by vmapping fit over a
leading fold axis whose per-fold weights mask the held-out fold.  Each
row of X is then read once and used by K-1 fits — strictly less data
movement than Ray's K independent tasks re-reading the dataset.

The zoo is MXU-native (DESIGN.md §2, §9): closed-form ridge, Newton
logistic and MLPs — replacing EconML's RandomForest defaults, which do
not map to systolic arrays.  The DML estimator is agnostic to the nuisance family as long as
it is consistent; tests verify the same ATE recovery.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import CausalConfig
from repro.core import moments
from repro.optim.adamw import adamw_init, adamw_update
from repro.config import TrainConfig


@dataclasses.dataclass(frozen=True, eq=False)
class Nuisance:
    """Pure-function model bundle.  All fns are jit/vmap-compatible.
    Identity-hashed (eq=False) so executor-facing closure caches can
    key on the instance (``hyper`` holds an unhashable dict).

    init(key, p)            -> state
    fit(state, X, y, w)     -> state      (w: (n,) sample weights)
    predict(state, X)       -> (n,)       (E[y|X] or P(t=1|X))

    ``hyper`` exposes the scalar hyper-parameters baked into the
    closures (lam, newton iters, ...) so repro.inference can rebuild the
    same fit on its replicate-invariant fold-batched kernels.
    """

    name: str
    task: str  # "reg" | "clf"
    init: Callable[[jax.Array, int], Any]
    fit: Callable[[Any, jax.Array, jax.Array, jax.Array], Any]
    predict: Callable[[Any, jax.Array], jax.Array]
    hyper: Optional[Dict[str, Any]] = None


def _aug(X: jax.Array) -> jax.Array:
    """Append the intercept column."""
    return jnp.concatenate(
        [X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)


# ---------------------------------------------------------------------------
# Ridge regression (closed form — one Gram + solve)
# ---------------------------------------------------------------------------

def make_ridge(lam: float = 1e-3, row_block: int = 0,
               strategy: Optional[str] = None) -> Nuisance:
    def init(key, p):
        return {"beta": jnp.zeros((p + 1,), jnp.float32),
                "lam": jnp.asarray(lam, jnp.float32)}

    def fit(state, X, y, w):
        # weighted normal equations as ONE augmented sufficient-
        # statistics pass (repro.core.moments): the target rides as an
        # appended design column, so G and the cross-moment b come out
        # of the same (optionally row-blocked) Gram reduction.
        q = X.shape[1] + 1
        Gaug, n_eff = moments.weighted_gram(X, w, intercept=True,
                                            append=y, row_block=row_block,
                                            strategy=strategy)
        n_eff = jnp.maximum(n_eff, 1.0)
        A = Gaug[:q, :q] / n_eff \
            + state["lam"] * jnp.eye(q, dtype=jnp.float32)
        beta = jnp.linalg.solve(A, Gaug[:q, q] / n_eff)
        return {**state, "beta": beta}

    def predict(state, X):
        return _aug(X.astype(jnp.float32)) @ state["beta"]

    return Nuisance("ridge", "reg", init, fit, predict,
                    hyper={"lam": lam, "row_block": row_block,
                           "strategy": strategy})


# ---------------------------------------------------------------------------
# Logistic regression via Newton/IRLS (fixed iteration count -> jit-able)
# ---------------------------------------------------------------------------

def make_logistic(lam: float = 1e-3, iters: int = 16,
                  row_block: int = 0,
                  strategy: Optional[str] = None) -> Nuisance:
    def init(key, p):
        return {"beta": jnp.zeros((p + 1,), jnp.float32),
                "lam": jnp.asarray(lam, jnp.float32)}

    def fit(state, X, y, w):
        Xf = X.astype(jnp.float32)
        ws = w.astype(jnp.float32)
        yt = y.astype(jnp.float32)
        q = X.shape[1] + 1
        n_eff = jnp.maximum(ws.sum(), 1.0)
        lam_eye = state["lam"] * jnp.eye(q, dtype=jnp.float32)

        def newton(_, beta):
            z = Xf @ beta[:-1] + beta[-1]
            mu = jax.nn.sigmoid(z)
            s = jnp.clip(mu * (1 - mu), 1e-6, None) * ws
            # Hessian + gradient in ONE weighted-moments pass over X
            H, g_raw, _ = moments.weighted_gram_and_vec(
                Xf, s, ws * (mu - yt), intercept=True,
                row_block=row_block, strategy=strategy)
            g = g_raw / n_eff + state["lam"] * beta
            return beta - jnp.linalg.solve(H / n_eff + lam_eye, g)

        beta = jax.lax.fori_loop(0, iters, newton, state["beta"])
        return {**state, "beta": beta}

    def predict(state, X):
        return jax.nn.sigmoid(_aug(X.astype(jnp.float32)) @ state["beta"])

    return Nuisance("logistic", "clf", init, fit, predict,
                    hyper={"lam": lam, "iters": iters,
                           "row_block": row_block, "strategy": strategy})


# ---------------------------------------------------------------------------
# MLP (full-batch AdamW for a fixed step count, lax.scan -> one program)
# ---------------------------------------------------------------------------

def _mlp_init(key, sizes) -> Dict[str, Any]:
    params = {}
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        kw, key = jax.random.split(key)
        params[f"w{i}"] = jax.random.normal(kw, (a, b), jnp.float32) / jnp.sqrt(a)
        params[f"b{i}"] = jnp.zeros((b,), jnp.float32)
    return params


def _mlp_forward(params, X, n_layers) -> jax.Array:
    h = X.astype(jnp.float32)
    for i in range(n_layers):
        h = h @ params[f"w{i}"] + params[f"b{i}"]
        if i < n_layers - 1:
            h = jax.nn.gelu(h)
    return h[..., 0]


def make_mlp(task: str, hidden: Tuple[int, ...] = (256, 256),
             steps: int = 200, lr: float = 1e-3, wd: float = 1e-4) -> Nuisance:
    tcfg = TrainConfig(learning_rate=lr, weight_decay=wd, grad_clip=1.0)
    n_layers = len(hidden) + 1

    def init(key, p):
        sizes = (p,) + tuple(hidden) + (1,)
        params = _mlp_init(key, sizes)
        return {"params": params, "opt": adamw_init(params)}

    def loss_fn(params, X, y, w):
        out = _mlp_forward(params, X, n_layers)
        if task == "clf":
            per = jnp.maximum(out, 0) - out * y + jnp.log1p(jnp.exp(-jnp.abs(out)))
        else:
            per = 0.5 * jnp.square(out - y)
        return jnp.sum(per * w) / jnp.maximum(w.sum(), 1.0)

    def fit(state, X, y, w):
        Xf = X.astype(jnp.float32)
        yf = y.astype(jnp.float32)
        wf = w.astype(jnp.float32)
        # an "lr" state leaf overrides the baked-in rate, so tuning can
        # sweep lr as DATA on one compiled program (core.tuning maps
        # trials through the executor without re-tracing per trial)
        lr_t = jnp.asarray(state.get("lr", lr), jnp.float32)

        def step(carry, _):
            params, opt = carry
            g = jax.grad(loss_fn)(params, Xf, yf, wf)
            params, opt, _ = adamw_update(g, opt, params, lr_t, tcfg)
            return (params, opt), None

        (params, opt), _ = jax.lax.scan(step, (state["params"], state["opt"]),
                                        None, length=steps)
        return {"params": params, "opt": opt}

    def predict(state, X):
        out = _mlp_forward(state["params"], X, n_layers)
        return jax.nn.sigmoid(out) if task == "clf" else out

    return Nuisance(f"mlp_{task}", task, init, fit, predict,
                    hyper={"hidden": hidden, "steps": steps, "lr": lr})


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

def make_nuisance(kind: str, task: str, cfg: CausalConfig) -> Nuisance:
    rb, st = cfg.row_block, cfg.row_block_strategy
    if kind == "ridge":
        return make_ridge(cfg.ridge_lambda, row_block=rb, strategy=st)
    if kind == "logistic":
        return make_logistic(cfg.ridge_lambda, cfg.newton_iters,
                             row_block=rb, strategy=st)
    if kind == "mlp":
        return make_mlp(task, cfg.mlp_hidden, cfg.mlp_steps, cfg.mlp_lr)
    raise ValueError(f"unknown nuisance kind {kind!r} "
                     "(expected ridge | logistic | mlp)")


# ---------------------------------------------------------------------------
# Fold-batched fast paths (beyond-paper optimization, EXPERIMENTS §Perf):
# the leave-one-out Gram identity
#
#       Xᵀ diag(w_k) X  =  G_total - G_heldout_k
#
# turns the K complement-weighted Grams of cross-fitting into ONE pass
# over X (a fold-segmented Gram) plus O(K p²) combination — the paper's
# C1 runs K tasks that each re-read the data; this removes even the
# single batched re-read per fold.  For logistic, the Newton/IRLS
# Hessians (16 X-passes) are replaced by the Böhning-Lindsay fixed
# majorizer H0 = XᵀX/4 + λI (factored once per fold via the same
# identity); iterations then cost two matvecs each.  Ridge stays EXACT;
# logistic converges monotonically to the same optimum (MM guarantee).
# ---------------------------------------------------------------------------

def _fold_grams(Xa: jax.Array, folds: jax.Array, k: int,
                row_block: int = 0, strategy: Optional[str] = None):
    """One-pass fold-segmented Gram: returns (G_heldout (k,p,p),
    G_total (p,p)).  Delegates to the moments engine (row_block > 0
    streams the pass in fixed-order row blocks)."""
    Gh, _ = moments.fold_gram(Xa, folds, k, row_block=row_block,
                              strategy=strategy)
    return Gh, Gh.sum(0)


def ridge_fit_folds(lam: float, X: jax.Array, y: jax.Array,
                    folds: jax.Array, k: int, row_block: int = 0,
                    strategy: Optional[str] = None):
    """EXACT per-fold ridge via the LOO identity; one X pass.  The
    target rides as an appended design column of the segmented Gram,
    so the per-fold cross-moments come out of the same reduction."""
    f32 = jnp.float32
    n, p = X.shape[0], X.shape[1] + 1
    Gh_aug, counts = moments.fold_gram(X, folds, k, intercept=True,
                                       append=y, row_block=row_block,
                                       strategy=strategy)
    G_aug = Gh_aug.sum(0)
    Gh, G = Gh_aug[:, :p, :p], G_aug[:p, :p]
    bh, b_tot = Gh_aug[:, :p, p], G_aug[:p, p]
    n_eff = jnp.maximum(n - counts, 1.0)[:, None, None]
    A = (G[None] - Gh) / n_eff + lam * jnp.eye(p, dtype=f32)[None]
    rhs = (b_tot[None] - bh) / n_eff[..., 0]
    beta = jnp.linalg.solve(A, rhs[..., None])[..., 0]      # (k, p)
    return {"beta": beta, "lam": jnp.full((k,), lam, f32)}


def logistic_fit_folds(lam: float, iters: int, X: jax.Array, t: jax.Array,
                       folds: jax.Array, k: int, row_block: int = 0,
                       strategy: Optional[str] = None):
    """Per-fold logistic via fixed-Hessian majorization (Böhning-Lindsay):
    H0_k = Xᵀdiag(w_k)X/4 + λI factored ONCE (LOO identity via one
    moments pass), then ``iters`` MM steps of two matvecs each."""
    f32 = jnp.float32
    Xa = _aug(X.astype(f32))
    n, p = Xa.shape
    Gh, G = _fold_grams(Xa, folds, k, row_block=row_block,
                        strategy=strategy)
    onehot = jax.nn.one_hot(folds, k, dtype=f32)            # (n, k)
    w = 1.0 - onehot                                        # train weights
    counts = onehot.sum(0)
    n_eff = jnp.maximum(n - counts, 1.0)
    H0 = (G[None] - Gh) / (4.0 * n_eff[:, None, None]) \
        + lam * jnp.eye(p, dtype=f32)[None]
    lu = jax.scipy.linalg.lu_factor(H0)
    tt = t.astype(f32)

    def step(_, beta):                                      # beta: (k, p)
        z = Xa @ beta.T                                     # (n, k)
        mu = jax.nn.sigmoid(z)
        r = w * (mu - tt[:, None])                          # (n, k)
        g = (r.T @ Xa) / n_eff[:, None] + lam * beta        # (k, p)
        delta = jax.vmap(jax.scipy.linalg.lu_solve)(lu, g[..., None])
        return beta - delta[..., 0]

    beta = jax.lax.fori_loop(0, iters, step, jnp.zeros((k, p), f32))
    return {"beta": beta, "lam": jnp.full((k,), lam, f32)}
