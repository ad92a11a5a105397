#!/usr/bin/env python3
"""Run the causal main path once on one TPU chip and check its answers.

    python chip_smoke.py                 # one chip: fit, sweep, store+serve
    python chip_smoke.py --chips 4       # the row-sharded data-mesh fit
    python chip_smoke.py --rehearse      # tiny shapes on the CPU (no result)

One process drives every phase through the entry points a user calls
(``CausalConfig`` -> ``DML`` / ``sweep`` / ``MomentStore`` ->
``EffectServer``).  Data comes from ``repro.data.causal_dgp`` seeded by
``--seed``; the program reads no file.

  fit    the paper's Fig. 6 top shape: DML, n = 1,000,000, p = 500,
         K = 5, ridge nuisances, row_block 8192 on the fused kernel
         (``row_block_strategy="pallas"``), pairs bootstrap B = 200 with
         ``runtime_memory_budget`` taken from the device's free memory.
  sweep  ``sweep(mode="segmented")`` over E = 64 segments x K = 5 folds at
         n = 1,000,000, p = 50, binary treatment (logistic MM
         propensity): the S = E*K = 320 kernel.
  store  a ``MomentStore`` DML column (all-ridge, continuous t, CATE
         basis [1, x0]) ingests three row_block-aligned days of 262,144
         rows, refreshes, saves to a ``CheckpointManager``;
         ``panel_from_checkpoint`` feeds an ``EffectServer`` that scores
         1,024 requests.

Each phase compares its timed outputs with a plain ``jax.numpy``
reference of the same estimand run on the same device under
``jax.default_matmul_precision("highest")``, and the effects with the
DGP's ground truth.  Each prints one line: shapes, the seg_gram
lowerings that ran, compile seconds apart from run seconds, checks.  A
phase also fails on any runtime retry or downgrade, any failed
compile-only probe, any ``seg_gram.fallback[...]`` and any failed
column.  The last line is ``{"ok": true, "device": {...}}`` only when
every phase passed; with no TPU the script exits non-zero first.

``--chips 4`` runs only the fit (cut to p = 50, B = 8) under
``use_data_mesh`` on four chips ("ordered" and "psum" reductions)
against the same fit on one device,
plus one sweep column that loses a shard and must show exactly one
downgrade.  Details of every run go to ``--out`` (default
``chiprun_out/chip_smoke/``).  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` here.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import shutil
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent

# Tolerances of the timed outputs against their "highest"-precision
# references.  Numerical error must be negligible against sampling
# error, so effects are compared in units of their own standard error.
TOL_THETA_SE = 0.05  # |theta - theta_ref| <= 0.05 se
TOL_SE_REL = 0.01  # |se - se_ref| <= 1% se_ref
TOL_MOMENT_REL = 1e-5  # ||G - G_ref||_F / ||G_ref||_F, f32 sums reassociated
TRUTH_SE = 5.0  # |theta - truth| <= 5 se (per cell; 5 sigma over 64 cells)
BOOT_SE_RATIO = (0.8, 1.25)  # bootstrap se / sandwich se at B = 200
SERVE_REL = 1e-5  # served CATE vs a float64 host recomputation


@dataclasses.dataclass(frozen=True)
class Sizes:
    fit_n: int = 1_000_000
    fit_p: int = 500
    folds: int = 5
    row_block: int = 8192
    boot: int = 200
    sweep_n: int = 1_000_000
    sweep_p: int = 50
    segments: int = 64
    day_n: int = 262_144
    days: int = 3
    store_p: int = 50
    requests: int = 1024
    ref_chunks: int = 64  # row chunks of the one-hot references


REHEARSAL = Sizes(fit_n=4096, fit_p=20, row_block=512, boot=16,
                  sweep_n=8192, sweep_p=10, segments=8, day_n=2048,
                  store_p=10, requests=96, ref_chunks=8)
# --chips 4 compares three fits (one device, then the mesh in "ordered"
# and "psum" mode), each compiling its own programs, so its fit is cut
# to p = 50 and a B = 8 bootstrap run as one batch; n, K and row_block
# keep the Fit's shape
FOUR_CHIP_CUT = {"fit_p": 50, "boot": 8}


# ---------------------------------------------------------------------------
# Measurement helpers
# ---------------------------------------------------------------------------

def _counters():
    from repro.obs.metrics import default_registry
    snap = default_registry().snapshot()
    return dict(snap["counters"]), dict(snap["gauges"])


def _delta(after, before, prefix):
    return {k[len(prefix):].strip("[]"): v - before.get(k, 0)
            for k, v in after.items()
            if k.startswith(prefix) and v - before.get(k, 0)}


class Phase:
    """Times one phase and collects its checks and health counters.
    Compile seconds, programs and persistent-cache hits come from the
    program's compile accounting (``repro.obs.trace``: the
    ``compile_s[...]``, ``compiles[...]`` and ``compile_cache_hits``
    counters of the process registry)."""

    def __init__(self, name):
        self.name = name
        self.info, self.checks, self.errors = {}, [], []
        self.timing = {"wall_s": 0.0, "compile_s": 0.0, "run_s": 0.0,
                       "compiles": 0, "cache_hits": 0}
        self.lowering, self.fallback, self.events = {}, {}, {}
        self.probe_failed, self.chunks, self.compiled = {}, {}, {}

    def __enter__(self):
        self.c0, self.g0 = _counters()
        self.t0 = time.perf_counter()
        return self

    def check(self, name, ok, detail):
        self.checks.append({"check": name, "ok": bool(ok), "detail": detail})

    def __exit__(self, et, ev, tb):
        wall = time.perf_counter() - self.t0
        c1, g1 = _counters()
        self.compiled = _delta(c1, self.c0, "compiles[")
        compile_s = sum(_delta(c1, self.c0, "compile_s[").values())
        self.timing = {"wall_s": wall, "compile_s": compile_s,
                       "run_s": wall - compile_s,
                       "compiles": sum(self.compiled.values()),
                       "cache_hits": c1.get("compile_cache_hits", 0)
                       - self.c0.get("compile_cache_hits", 0)}
        self.lowering = _delta(c1, self.c0, "seg_gram.lowering")
        self.fallback = _delta(c1, self.c0, "seg_gram.fallback")
        self.events = _delta(c1, self.c0, "runtime.events.")
        self.probe_failed = _delta(c1, self.c0, "runtime.probe_failed")
        self.chunks = {k: v for k, v in g1.items()
                       if k.startswith("runtime.chunk_size")}
        if et is not None and issubclass(et, Exception):
            self.errors.append("".join(
                traceback.format_exception(et, ev, tb))[-2000:])
            return True  # the phase records its failure; others still run
        return False

    def health(self, allow_fallback=False, allowed_events=()):
        bad = {k: v for k, v in self.events.items()
               if k in ("retry", "downgrade") and k not in allowed_events}
        self.check("no_retry_or_downgrade", not bad, bad or "none")
        self.check("probes_ran", not self.probe_failed,
                   self.probe_failed or "none failed")
        if not allow_fallback:
            self.check("no_seg_gram_fallback", not self.fallback,
                       self.fallback or "none")

    @property
    def passed(self):
        return not self.errors and bool(self.checks) and all(
            c["ok"] for c in self.checks)

    def record(self):
        return {"phase": self.name, "passed": self.passed, **self.info,
                "timing": self.timing, "compiles_by_span": self.compiled,
                "lowering": self.lowering,
                "fallback": self.fallback, "runtime_events": self.events,
                "chunk_size": self.chunks, "checks": self.checks,
                "errors": self.errors}

    def line(self):
        t = self.timing
        shapes = " ".join(f"{k}={v}" for k, v in self.info.items()
                          if k in ("n", "p", "K", "E", "S", "B", "row_block",
                                   "days", "day_n", "requests", "mesh"))
        bad = [c["check"] for c in self.checks if not c["ok"]]
        verdict = "PASS" if self.passed else (
            "FAIL " + (",".join(bad) if bad else "error"))
        return (f"{self.name}: {shapes} | lowering={self.lowering} "
                f"fallback={self.fallback} events={self.events} | "
                f"compile_s={t['compile_s']:.2f} run_s={t['run_s']:.2f} "
                f"compiles={t['compiles']} cache_hits={t['cache_hits']} | "
                f"{self.info.get('summary', '')} | {verdict}")


def _rel(a, b):
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _within_se(got, ref, se, k):
    """max |got - ref| / se (elementwise) and whether it is <= k."""
    import numpy as np
    d = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    r = float(np.max(d / np.maximum(np.asarray(se, np.float64), 1e-30)))
    return r, r <= k


# ---------------------------------------------------------------------------
# Plain jax.numpy references (run under default_matmul_precision("highest"))
# ---------------------------------------------------------------------------

def _seg_outer_ref(U, V, ids, n_seg, chunks):
    """sum_{ids_n = s} U_n (x) V_n -> (n_seg, qU, qV): one-hot matmuls
    over row chunks (the one-hot is 0/1, exact at any precision)."""
    import jax
    import jax.numpy as jnp
    n, qu, qv = U.shape[0], U.shape[1], V.shape[1]
    m = n // chunks

    def step(acc, c):
        u = jax.lax.dynamic_slice_in_dim(U, c * m, m)
        v = jax.lax.dynamic_slice_in_dim(V, c * m, m)
        i = jax.lax.dynamic_slice_in_dim(ids, c * m, m)
        outer = (u[:, :, None] * v[:, None, :]).reshape(m, qu * qv)
        return acc + jax.nn.one_hot(i, n_seg, dtype=jnp.float32).T @ outer, None

    acc, _ = jax.lax.scan(step, jnp.zeros((n_seg, qu * qv), jnp.float32),
                          jnp.arange(chunks))
    return acc.reshape(n_seg, qu, qv)


def _ref_fold_ids(key, n, k):
    import jax
    import jax.numpy as jnp
    return jax.random.permutation(key, jnp.arange(n, dtype=jnp.int32) % k)


def _ref_dml(X, y, t, key, k, lam, w, chunks):
    """Weighted cross-fit DML with ridge nuisances, directly from its
    definition: per fold, ridge on the complement rows (weights w),
    out-of-fold predictions, then the orthogonal final stage with its
    HC0 standard error.  Returns (theta, se)."""
    import jax
    import jax.numpy as jnp
    n = X.shape[0]
    kf, _, _ = jax.random.split(key, 3)
    folds = _ref_fold_ids(kf, n, k)
    D = jnp.concatenate([X, jnp.ones((n, 1), X.dtype), y[:, None],
                         t[:, None]], axis=1)
    q = X.shape[1] + 1
    Wc = w[:, None] * (folds[:, None] != jnp.arange(k)[None, :])  # (n, k)
    m = n // chunks

    def step(acc, c):
        d = jax.lax.dynamic_slice_in_dim(D, c * m, m)
        wc = jax.lax.dynamic_slice_in_dim(Wc, c * m, m)
        return acc + jnp.einsum("mk,mi,mj->kij", wc, d, d), None

    G, _ = jax.lax.scan(step, jnp.zeros((k, q + 2, q + 2), jnp.float32),
                        jnp.arange(chunks))
    ne = jnp.maximum(Wc.sum(0), 1.0)[:, None]
    A = G[:, :q, :q] / ne[..., None] + lam * jnp.eye(q, dtype=jnp.float32)
    B = jnp.linalg.solve(A, G[:, :q, q:] / ne[..., None])  # (k, q, 2)
    pred_y = jnp.take_along_axis(D[:, :q] @ B[..., 0].T, folds[:, None], 1)
    pred_t = jnp.take_along_axis(D[:, :q] @ B[..., 1].T, folds[:, None], 1)
    ry = y - pred_y[:, 0]
    rt = t - pred_t[:, 0]
    a = (w * rt * rt).sum() + 1e-8 * jnp.maximum(w.sum(), 1.0)
    theta = (w * rt * ry).sum() / a
    e = ry - theta * rt
    se = jnp.sqrt(((w * e * rt) ** 2).sum()) / a
    return theta, se


def _ref_segmented_dml(X, y, t, sids, E, k, lam, iters, key, chunks):
    """Per-segment DML with one shared fold assignment: ridge y and
    Boehning-Lindsay MM logistic t per (segment, fold complement), per
    segment final stage + HC0 — from one-hot reference Grams."""
    import jax
    import jax.numpy as jnp
    n = X.shape[0]
    folds = _ref_fold_ids(key, n, k)
    comb = sids * k + folds
    Xa = jnp.concatenate([X, jnp.ones((n, 1), jnp.float32)], axis=1)
    q = Xa.shape[1]
    D = jnp.concatenate([Xa, y[:, None]], axis=1)
    Gh = _seg_outer_ref(D, D, comb, E * k, chunks).reshape(E, k, q + 1, q + 1)
    cnt = jax.ops.segment_sum(jnp.ones((n,)), comb, E * k).reshape(E, k)
    Gc = Gh.sum(1, keepdims=True) - Gh
    ne = jnp.maximum(cnt.sum(1, keepdims=True) - cnt, 1.0)
    eye = jnp.eye(q, dtype=jnp.float32)
    A = Gc[..., :q, :q] / ne[..., None, None] + lam * eye
    beta_y = jnp.linalg.solve(A, (Gc[..., :q, q] / ne[..., None])[..., None])[..., 0]
    H0 = Gc[..., :q, :q] / (4.0 * ne[..., None, None]) + lam * eye

    def mm(_, beta):
        mu = jax.nn.sigmoid(jnp.einsum("np,nkp->nk", Xa, beta[sids]))
        r = mu - t[:, None]
        rr = jnp.take_along_axis(r, folds[:, None], axis=1)[:, 0]
        t1 = _seg_outer_ref(r, Xa, sids, E, chunks)
        t2 = _seg_outer_ref(rr[:, None], Xa, comb, E * k, chunks)
        g = (t1 - t2.reshape(E, k, q)) / ne[..., None] + lam * beta
        return beta - jnp.linalg.solve(H0, g[..., None])[..., 0]

    beta_t = jax.lax.fori_loop(0, iters, mm, jnp.zeros((E, k, q), jnp.float32))
    ry = y - (Xa * beta_y[sids, folds]).sum(1)
    rt = t - jax.nn.sigmoid((Xa * beta_t[sids, folds]).sum(1))
    m = jnp.stack([rt, ry], axis=1)
    g = _seg_outer_ref(m, m, sids, E, chunks)
    nseg = jnp.maximum(jax.ops.segment_sum(jnp.ones((n,)), sids, E), 1.0)
    a = g[:, 0, 0] + 1e-8 * nseg
    theta = g[:, 0, 1] / a
    me = ((ry - theta[sids] * rt) * rt)[:, None]
    meat = _seg_outer_ref(me, me, sids, E, chunks)[:, 0, 0]
    return theta, jnp.sqrt(meat) / a


def _ref_store_dml(X, t, y, sids, folds, E, k, lam, chunks):
    """The store's estimand from the rows: per (segment, fold
    complement) ridge nuisances on [X | 1], residuals, per-segment final
    stage on phi = [1, x0] with the homoskedastic sandwich.  Returns
    (theta (E, 2), se (E, 2), the reference nuisance fold Gram)."""
    import jax
    import jax.numpy as jnp
    n = X.shape[0]
    comb = sids * k + folds
    Xa = jnp.concatenate([X, jnp.ones((n, 1), jnp.float32)], axis=1)
    q = Xa.shape[1]
    dn = jnp.concatenate([Xa, t[:, None], y[:, None]], axis=1)
    ng = _seg_outer_ref(dn, dn, comb, E * k, chunks)
    G = ng.reshape(E, k, q + 2, q + 2)
    cnt = jax.ops.segment_sum(jnp.ones((n,)), comb, E * k).reshape(E, k)
    Gc = G.sum(1, keepdims=True) - G
    ne = jnp.maximum(cnt.sum(1, keepdims=True) - cnt, 1.0)
    A = Gc[..., :q, :q] / ne[..., None, None] + lam * jnp.eye(q)
    B = jnp.linalg.solve(A, Gc[..., :q, q:] / ne[..., None, None])  # (E,k,q,2)
    pred = jnp.einsum("np,npc->nc", Xa, B[sids, folds])
    rt, ry = t - pred[:, 0], y - pred[:, 1]
    phi = jnp.stack([jnp.ones((n,)), X[:, 0]], axis=1)
    z = rt[:, None] * phi
    m = jnp.concatenate([z, ry[:, None]], axis=1)
    g = _seg_outer_ref(m, m, sids, E, chunks)
    nseg = jnp.maximum(jax.ops.segment_sum(jnp.ones((n,)), sids, E), 1.0)
    Gzz = g[:, :2, :2]
    a = Gzz + 1e-8 * nseg[:, None, None] * jnp.eye(2)
    theta = jnp.linalg.solve(a, g[:, :2, 2:])[..., 0]
    sse = jax.ops.segment_sum((ry - (z * theta[sids]).sum(1)) ** 2, sids, E)
    ainv = jnp.linalg.inv(a)
    cov = (sse / nseg)[:, None, None] * (ainv @ Gzz @ ainv)
    se = jnp.sqrt(jnp.clip(jnp.diagonal(cov, axis1=1, axis2=2), 0.0, None))
    return theta, se, ng


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def _memory_budget(jax, dev):
    """Replicate-batch budget: 60% of the device memory still free."""
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    if not limit:
        return 64 << 20  # the CPU rehearsal: small enough to force chunks
    return int(0.6 * (limit - stats.get("bytes_in_use", 0)))


def phase_fit(ph, sz, seed, dev):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.config import CausalConfig
    from repro.core.dml import DML
    from repro.data.causal_dgp import make_causal_data

    key = jax.random.PRNGKey(seed)
    data = make_causal_data(jax.random.fold_in(key, 1), sz.fit_n, sz.fit_p,
                            effect=1.0)
    jax.block_until_ready(data.X)
    budget = _memory_budget(jax, dev)
    cfg = CausalConfig(n_folds=sz.folds, nuisance_y="ridge",
                       nuisance_t="ridge", row_block=sz.row_block,
                       row_block_strategy="pallas", inference="bootstrap",
                       n_bootstrap=sz.boot, runtime_memory_budget=budget)
    ph.info.update(n=sz.fit_n, p=sz.fit_p, K=sz.folds, B=sz.boot,
                   row_block=sz.row_block, memory_budget=budget)
    fit_key = jax.random.fold_in(key, 2)
    with ph:
        t0 = time.perf_counter()
        res = DML(cfg).fit(data.y, data.t, data.X, key=fit_key)
        theta = float(res.theta[0])
        se = float(res.stderr[0])
        ph.info["fit_s"] = time.perf_counter() - t0
        t1 = time.perf_counter()
        lo, hi = res.ate_interval()
        inf = res.inference()
        reps = np.asarray(jax.block_until_ready(inf.replicates))[:, 0]
        ph.info["bootstrap_s"] = time.perf_counter() - t1
    ph.health()
    if ph.errors:
        return
    with jax.default_matmul_precision("highest"):
        ones = jnp.ones((sz.fit_n,), jnp.float32)
        ref = jax.jit(_ref_dml, static_argnums=(4, 7))
        th_ref, se_ref = ref(data.X, data.y, data.t, fit_key, sz.folds,
                             cfg.ridge_lambda, ones, sz.ref_chunks)
        # replicates 0 and 1, re-derived from the bootstrap's key lineage
        bkey = jax.random.fold_in(fit_key, 0x0B00)
        rep_ref = []
        for b in range(2):
            kw, kfit = jax.random.split(jax.random.fold_in(bkey, b))
            idx = jax.random.randint(kw, (sz.fit_n,), 0, sz.fit_n)
            w = jnp.bincount(idx, length=sz.fit_n).astype(jnp.float32)
            rep_ref.append(float(ref(data.X, data.y, data.t, kfit, sz.folds,
                                     cfg.ridge_lambda, w, sz.ref_chunks)[0]))
    th_ref, se_ref = float(th_ref), float(se_ref)
    boot_se = float(np.std(reps, ddof=1))
    r, ok = _within_se(theta, th_ref, se_ref, TOL_THETA_SE)
    ph.check("theta_vs_ref", ok, f"|d|/se={r:.4g} (theta={theta:.6f} "
             f"ref={th_ref:.6f})")
    ph.check("se_vs_ref", abs(se - se_ref) <= TOL_SE_REL * se_ref,
             f"se={se:.6g} ref={se_ref:.6g}")
    r, ok = _within_se(reps[:2], rep_ref, boot_se, TOL_THETA_SE)
    ph.check("replicates_vs_ref", ok, f"|d|/boot_se={r:.4g}")
    z = abs(theta - data.true_ate) / se
    ph.check("true_ate", z <= TRUTH_SE, f"|theta-true|/se={z:.3f}")
    ratio = boot_se / se
    ph.check("bootstrap_se", BOOT_SE_RATIO[0] <= ratio <= BOOT_SE_RATIO[1],
             f"boot_se/se={ratio:.3f}")
    ph.check("ci_covers_theta", lo <= theta <= hi,
             f"ci=[{lo:.5f}, {hi:.5f}]")
    ph.info["summary"] = (f"theta={theta:.6f} se={se:.6f} ref={th_ref:.6f} "
                          f"ci=[{lo:.5f},{hi:.5f}] boot_se={boot_se:.6f} "
                          f"fit_s={ph.info['fit_s']:.2f} "
                          f"bootstrap_s={ph.info['bootstrap_s']:.2f} "
                          f"chunk={ph.chunks}")


def phase_sweep(ph, sz, seed):
    import jax
    import numpy as np

    from repro.config import CausalConfig
    from repro.data.causal_dgp import make_causal_data
    from repro.sweep import SweepSpec, sweep

    key = jax.random.PRNGKey(seed + 1)
    data = make_causal_data(jax.random.fold_in(key, 1), sz.sweep_n,
                            sz.sweep_p, effect=1.0)
    E, K = sz.segments, sz.folds
    sids = jax.random.randint(jax.random.fold_in(key, 2), (sz.sweep_n,), 0, E)
    cfg = CausalConfig(n_folds=K, row_block=sz.row_block,
                       row_block_strategy="pallas", inference="none")
    spec = SweepSpec(n_segments=E, columns=(("dml", cfg),))
    skey = jax.random.fold_in(key, 3)
    ph.info.update(n=sz.sweep_n, p=sz.sweep_p, E=E, K=K, S=E * K,
                   row_block=sz.row_block)
    with ph:
        panel = sweep(spec, X=data.X, y=data.y, t=data.t, segment_ids=sids,
                      key=skey, mode="segmented")
        col = panel.columns[0]
        thetas = np.asarray(jax.block_until_ready(col.thetas))[:, 0]
        ses = np.asarray(col.ses)[:, 0]
    ph.health()
    if ph.errors:
        return
    ph.check("no_failed_column", not panel.failures(), panel.failures())
    ph.check("segmented_path", col.events == ("segmented",), col.events)
    with jax.default_matmul_precision("highest"):
        th_ref, se_ref = jax.jit(
            _ref_segmented_dml, static_argnums=(4, 5, 7, 9))(
            data.X, data.y, data.t, sids, E, K, cfg.ridge_lambda,
            2 * cfg.newton_iters, jax.random.fold_in(skey, 0), sz.ref_chunks)
    th_ref, se_ref = np.asarray(th_ref), np.asarray(se_ref)
    r, ok = _within_se(thetas, th_ref, se_ref, TOL_THETA_SE)
    ph.check("theta_vs_ref", ok, f"max |d|/se={r:.4g}")
    srel = float(np.max(np.abs(ses - se_ref) / se_ref))
    ph.check("se_vs_ref", srel <= TOL_SE_REL, f"max rel={srel:.3g}")
    r, ok = _within_se(thetas, np.full_like(thetas, data.true_ate), ses,
                       TRUTH_SE)
    ph.check("true_ate", ok, f"max |theta-true|/se={r:.3f}")
    ph.info["summary"] = (f"theta mean={thetas.mean():.5f} "
                          f"[{thetas.min():.4f},{thetas.max():.4f}] "
                          f"se mean={ses.mean():.5f} max|d|/se vs ref="
                          f"{_within_se(thetas, th_ref, se_ref, 1)[0]:.3g}")


def phase_store_serve(ph, sz, seed, out):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.manager import CheckpointManager
    from repro.config import CausalConfig
    from repro.data.causal_dgp import make_causal_data
    from repro.inference.intervals import z_crit
    from repro.serve_effects import EffectServer, panel_from_checkpoint
    from repro.serve_effects.scoring import score_single
    from repro.store import MomentStore
    from repro.sweep.spec import SweepSpec

    key = jax.random.PRNGKey(seed + 2)
    N, p, E, K = sz.days * sz.day_n, sz.store_p, sz.segments, sz.folds
    data = make_causal_data(jax.random.fold_in(key, 1), N, p, effect=1.0,
                            discrete_treatment=False, heterogeneous=True)
    sids = jax.random.randint(jax.random.fold_in(key, 2), (N,), 0, E)
    cfg = CausalConfig(n_folds=K, nuisance_y="ridge", nuisance_t="ridge",
                       discrete_treatment=False, cate_features=2,
                       row_block=sz.row_block, row_block_strategy="pallas",
                       inference="none")
    spec = SweepSpec(n_segments=E, columns=(("dml", cfg),))
    skey = jax.random.fold_in(key, 3)
    rq = jax.random.split(jax.random.fold_in(key, 4), 2)
    Xr = np.asarray(jax.random.normal(rq[0], (sz.requests, p)), np.float32)
    sr = np.asarray(jax.random.randint(rq[1], (sz.requests,), 0, E))
    ckpt = out / "store_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    ph.info.update(p=p, E=E, K=K, S=E * K, days=sz.days, day_n=sz.day_n,
                   row_block=sz.row_block, requests=sz.requests)
    with ph:
        store = MomentStore(spec, n_features=p, key=skey)
        t0 = time.perf_counter()
        for d in range(sz.days):
            s = slice(d * sz.day_n, (d + 1) * sz.day_n)
            store.ingest(X=data.X[s], y=data.y[s], t=data.t[s],
                         segment_ids=sids[s])
        panel = store.refresh()
        col = panel.columns[0]
        jax.block_until_ready(col.thetas)
        ph.info["ingest_refresh_s"] = time.perf_counter() - t0
        mgr = CheckpointManager(str(ckpt), keep_latest=2)
        store.save(mgr)
        serving = panel_from_checkpoint(mgr, spec, p, key=skey)
        server = EffectServer(serving, wave_sizes=(64, 256),
                              max_queue=sz.requests)
        t1 = time.perf_counter()
        resp = server.score(Xr, sr)
        ph.info["serve_s"] = time.perf_counter() - t1
        zc = z_crit(server.alpha)
        single = [score_single(serving, Xr[i], int(sr[i]), zc)
                  for i in range(sz.requests)]
        single = [{k: np.asarray(v) for k, v in s.items()} for s in single]
    ph.health()
    if ph.errors:
        return
    ph.check("no_failed_column", not panel.failures(), panel.failures())
    ph.check("aligned_ingests", bool(col.aligned), col.aligned)
    thetas, ses = np.asarray(col.thetas), np.asarray(col.ses)
    ph.check("checkpoint_roundtrip",
             np.array_equal(np.asarray(serving.thetas), thetas)
             and np.array_equal(np.asarray(serving.ses), ses),
             "served panel == refreshed panel")
    fields = ("cate", "lo", "hi", "se", "ok")
    mism = sum(any(getattr(r, f) != s[f].item() for f in fields)
               for r, s in zip(resp, single))
    ph.check("batched_equals_single", mism == 0,
             f"{mism}/{sz.requests} responses differ from score_single")
    th64, x0 = thetas.astype(np.float64), Xr[:, 0].astype(np.float64)
    cate64 = th64[sr, 0] + th64[sr, 1] * x0
    cate = np.array([r.cate for r in resp])
    srv = float(np.max(np.abs(cate - cate64) / (1.0 + np.abs(cate64))))
    ph.check("served_cate", srv <= SERVE_REL and all(r.ok for r in resp),
             f"max rel={srv:.3g}")
    # reference: the same folds (index-keyed), the estimand from the rows
    col_key = jax.random.fold_in(skey, 0)
    folds = jax.vmap(lambda i: jax.random.randint(
        jax.random.fold_in(col_key, i), (), 0, K))(
        jnp.arange(N, dtype=jnp.uint32)).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        th_ref, se_ref, ng_ref = jax.jit(
            _ref_store_dml, static_argnums=(5, 6, 8))(
            data.X, data.t, data.y, sids, folds, E, K, cfg.ridge_lambda,
            sz.ref_chunks)
    ng = store.state_dict()["col0"]["ng"]
    mrel = _rel(ng, ng_ref)
    ph.check("moments_vs_ref", mrel <= TOL_MOMENT_REL, f"rel={mrel:.3g}")
    th_ref, se_ref = np.asarray(th_ref), np.asarray(se_ref)
    r, ok = _within_se(thetas, th_ref, se_ref, TOL_THETA_SE)
    ph.check("theta_vs_ref", ok, f"max |d|/se={r:.4g}")
    srel = float(np.max(np.abs(ses - se_ref) / se_ref))
    ph.check("se_vs_ref", srel <= TOL_SE_REL, f"max rel={srel:.3g}")
    truth = np.broadcast_to(np.array([1.0, 0.5]), thetas.shape)
    r, ok = _within_se(thetas, truth, ses, TRUTH_SE)
    ph.check("true_cate", ok, f"max |theta-[1,.5]|/se={r:.3f}")
    lat = server.snapshot()["histograms"].get("serve.request_seconds", {})
    ph.info["summary"] = (
        f"theta mean={thetas.mean(0).round(4).tolist()} "
        f"ingest+refresh_s={ph.info['ingest_refresh_s']:.2f} "
        f"serve_s={ph.info['serve_s']:.3f} "
        f"request p50={lat.get('p50', float('nan')):.4g}s "
        f"p99={lat.get('p99', float('nan')):.4g}s")


def phase_mesh_fit(ph, sz, seed, n_dev):
    """The fit on one device, then row-sharded over n_dev devices in
    "ordered" and "psum" mode; the ordered mesh fit is documented as
    bitwise the single-device chunked fit."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.config import CausalConfig
    from repro.core.dml import DML
    from repro.data.causal_dgp import make_causal_data
    from repro.runtime.distributed import (DATA_AXES, make_data_mesh,
                                           use_data_mesh)

    key = jax.random.PRNGKey(seed)
    data = make_causal_data(jax.random.fold_in(key, 1), sz.fit_n, sz.fit_p,
                            effect=1.0)
    meshes = {m: make_data_mesh(reduction=m) for m in ("ordered", "psum")}
    dm = meshes["ordered"]
    probe = jax.device_put(np.zeros((dm.n_shards * 8,), np.float32),
                           NamedSharding(dm.mesh, P(DATA_AXES)))
    span = sorted({s.device.id for s in probe.addressable_shards})
    ph.check("mesh_spans_devices", dm.n_shards == n_dev and len(span) == n_dev,
             f"n_shards={dm.n_shards} devices={span}")
    # the chunked strategy: its ordered mesh reduction is the bitwise
    # twin of the single-device blocked fold (the pallas strategy would
    # swap its kernel for the sharded scatter lowering under a mesh)
    cfg = CausalConfig(n_folds=sz.folds, nuisance_y="ridge",
                       nuisance_t="ridge", row_block=sz.row_block,
                       row_block_strategy="chunked", inference="bootstrap",
                       n_bootstrap=sz.boot, runtime_chunk=sz.boot)
    ph.info.update(n=sz.fit_n, p=sz.fit_p, K=sz.folds, B=sz.boot,
                   row_block=sz.row_block, mesh=f"{n_dev}x:ordered,psum")
    fit_key = jax.random.fold_in(key, 2)
    out, secs = {}, {}
    with ph:
        for mode in ("single", "ordered", "psum"):
            dmm = meshes.get(mode)
            t0 = time.perf_counter()
            with use_data_mesh(dmm):
                res = DML(cfg).fit(data.y, data.t, data.X, key=fit_key)
                inf = res.inference()
            out[mode] = (np.asarray(res.theta), np.asarray(res.stderr),
                         np.asarray(inf.replicates))
            secs[mode] = time.perf_counter() - t0
    ph.health()
    if ph.errors:
        return
    base = out["single"]
    bitwise = all(np.array_equal(a, b) for a, b in zip(out["ordered"], base))
    ph.info["ordered_bitwise"] = bitwise
    d_ord = max(float(np.max(np.abs(a - b))) for a, b in zip(out["ordered"], base))
    d_psum = max(float(np.max(np.abs(a - b))) for a, b in zip(out["psum"], base))
    se = float(base[1][0])
    ph.check("ordered_vs_single", d_ord <= TOL_THETA_SE * se,
             f"bitwise={bitwise} max|d|={d_ord:.3g}")
    ph.check("psum_vs_single", d_psum <= TOL_THETA_SE * se,
             f"max|d|={d_psum:.3g}")
    z = abs(float(base[0][0]) - data.true_ate) / se
    ph.check("true_ate", z <= TRUTH_SE, f"|theta-true|/se={z:.3f}")
    ph.info["summary"] = (f"theta={float(base[0][0]):.6f} se={se:.6f} "
                          f"ordered bitwise={bitwise} max|d| ordered="
                          f"{d_ord:.3g} psum={d_psum:.3g} seconds="
                          + ",".join(f"{k}:{v:.2f}" for k, v in secs.items()))


def phase_lost_shard(ph, sz, seed):
    """One sweep column over the mesh loses a shard: exactly one
    downgrade, and the column lands bitwise the single-host panel."""
    import jax
    import numpy as np

    from repro.config import CausalConfig
    from repro.data.causal_dgp import make_causal_data
    from repro.runtime.distributed import inject_shard_failure, make_data_mesh
    from repro.sweep import SweepSpec, sweep

    key = jax.random.PRNGKey(seed + 3)
    n, p, E = 8 * sz.row_block, sz.sweep_p, 8
    data = make_causal_data(jax.random.fold_in(key, 1), n, p, effect=1.0)
    sids = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, E)
    cfg = CausalConfig(n_folds=sz.folds, row_block=sz.row_block,
                       inference="none")
    spec = SweepSpec(n_segments=E, columns=(("dml", cfg),))
    kw = dict(X=data.X, y=data.y, t=data.t, segment_ids=sids, key=key)
    dm = make_data_mesh()
    ph.info.update(n=n, p=p, E=E, row_block=sz.row_block, mesh=dm.label)
    with ph:
        plain = sweep(spec, **kw).columns[0]
        inject_shard_failure(1)
        try:
            struck = sweep(spec, data_mesh=dm, **kw).columns[0]
        finally:
            inject_shard_failure(0)
    ph.health(allowed_events=("retry", "downgrade"))
    if ph.errors:
        return
    downs = [e for e in struck.events if e.startswith("downgrade:")]
    ph.check("one_downgrade", len(downs) == 1 and not struck.failed,
             struck.events)
    ph.check("bitwise_after_downgrade",
             np.array_equal(np.asarray(plain.thetas), np.asarray(struck.thetas)),
             "struck column == single-host column")
    ph.info["summary"] = f"events={struck.events}"


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def _run(ph, runner):
    """Run one phase; an error outside its timed block (data, reference)
    fails the phase, not the script."""
    try:
        runner(ph)
    except Exception:  # noqa: BLE001 — reported on the phase line
        ph.errors.append(traceback.format_exc()[-2000:])
    return ph


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the data-mesh fit and lost-shard column")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU with interpret-mode "
                         "kernels; never reports a chip result")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"))
    args = ap.parse_args(argv)

    if args.rehearse and args.chips == 4:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); this "
              "script measures the chip only (--rehearse runs tiny shapes "
              "on the CPU)", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devs)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels.seg_gram import ops as sg_ops

    sz = REHEARSAL if args.rehearse else Sizes()
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    from repro.obs.trace import process_tracer
    process_tracer()  # installs the compile accounting the phases read
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    lowering = sg_ops.force_backend("interpret") if args.rehearse else None
    if lowering is not None:
        lowering.__enter__()
    if args.chips == 4:
        fsz = dataclasses.replace(sz, **{k: min(getattr(sz, k), v)
                                         for k, v in FOUR_CHIP_CUT.items()})
        runners = {"mesh_fit": lambda ph: phase_mesh_fit(ph, fsz, args.seed,
                                                         args.chips),
                   "lost_shard": lambda ph: phase_lost_shard(ph, sz,
                                                             args.seed)}
    else:
        runners = {"fit": lambda ph: phase_fit(ph, sz, args.seed, devs[0]),
                   "sweep": lambda ph: phase_sweep(ph, sz, args.seed),
                   "store": lambda ph: phase_store_serve(ph, sz, args.seed,
                                                         out)}
    phases = []
    for name, runner in runners.items():
        # each line as its phase ends, so a run cut short still reports
        phases.append(_run(Phase(name), runner))
        print(phases[-1].line(), flush=True)
    ok = bool(phases) and all(ph.passed for ph in phases)
    stamp = time.strftime("%Y%m%dT%H%M%S")
    record = {"ok": ok, "device": device, "rehearsal": args.rehearse,
              "compile_cache": jax.config.jax_compilation_cache_dir,
              "phases": [ph.record() for ph in phases]}
    (out / f"run_{stamp}_chips{args.chips}.json").write_text(
        json.dumps(record, indent=2, default=str))
    if args.rehearse:
        print(f"chip_smoke rehearsal on {platform}: "
              f"{'all phases passed' if ok else 'FAILED'} (not a chip result)")
        return 0 if ok else 1
    if not ok:
        print(json.dumps({"ok": False, "device": device,
                          "failed": [ph.name for ph in phases
                                     if not ph.passed]}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
