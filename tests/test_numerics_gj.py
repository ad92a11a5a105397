"""Panelled Gauss-Jordan in ``repro.inference.numerics``: ``det_solve``
and ``det_inv`` equal the unblocked elimination bit for bit, at every
size around the panel width and under 0, 1 and 2 vmap axes, and the
trace-time counter names the path each size takes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.inference import numerics
from repro.inference.numerics import det_inv, det_solve
from repro.obs.metrics import default_registry


def _step(i, M):
    """One column of the unblocked elimination (the loop det_solve ran
    before panels), kept here as the oracle."""
    piv = M[i] / M[i, i]
    factors = M[:, i].at[i].set(0.0)
    M = M - factors[:, None] * piv[None, :]
    return M.at[i].set(piv)


def _oracle_solve(A, b):
    M = jnp.concatenate([A, b[:, None]], axis=1)
    return jax.lax.fori_loop(0, A.shape[0], _step, M)[:, -1]


def _oracle_inv(A):
    p = A.shape[0]
    M = jnp.concatenate([A, jnp.eye(p, dtype=A.dtype)], axis=1)
    return jax.lax.fori_loop(0, p, _step, M)[:, p:]


def _ridge_systems(p, batch, seed):
    """Ridge normal equations (X'X/n + lambda I) and right-hand sides."""
    kx, kb = jax.random.split(jax.random.PRNGKey(seed))
    X = jax.random.normal(kx, batch + (2 * p + 8, p))
    A = (jnp.einsum("...np,...nq->...pq", X, X) / X.shape[-2]
         + 1e-3 * jnp.eye(p))
    return A, jax.random.normal(kb, batch + (p,))


def _batched(fn, depth):
    for _ in range(depth):
        fn = jax.vmap(fn)
    return jax.jit(fn)


def _bits(x):
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("batch", [(), (5,), (2, 5)],
                         ids=["single", "vmap", "vmap2"])
@pytest.mark.parametrize("p", [1, 5, 16, 17, 31, 32, 33, 51, 64, 65, 106, 501])
def test_matches_unblocked_bitwise(p, batch):
    A, b = _ridge_systems(p, batch, seed=p)
    d = len(batch)
    x = _batched(det_solve, d)(A, b)
    Ainv = _batched(det_inv, d)(A)
    np.testing.assert_array_equal(_bits(x),
                                  _bits(_batched(_oracle_solve, d)(A, b)))
    np.testing.assert_array_equal(_bits(Ainv),
                                  _bits(_batched(_oracle_inv, d)(A)))
    # one trace each of det_solve and det_inv, on the path the size picks
    path, other = (("panelled", "unblocked") if p > numerics._UNBLOCKED_MAX
                   else ("unblocked", "panelled"))
    counters = default_registry().snapshot()["counters"]
    assert counters.get(f"det_solve.path[{path}]") == 2
    assert f"det_solve.path[{other}]" not in counters


@pytest.mark.parametrize("scope,fn,args", [
    ("det_solve", jax.vmap(det_solve),
     (jnp.eye(40)[None].repeat(3, 0) * 2.0, jnp.ones((3, 40)))),
    ("det_inv", jax.vmap(det_inv), (jnp.eye(40)[None].repeat(3, 0),)),
])
def test_panelled_ops_carry_the_scope(scope, fn, args):
    """Every op the panelled path puts in the program names its scope
    (the reducers' scalar bodies and the arguments name none)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.startswith("jit(")]
    assert names and all(scope in n for n in names), \
        [n for n in names if scope not in n][:5]


def _one_column_det_solve(A, b):
    """``det_solve`` as it was before it took several right-hand sides:
    the oracle of its one-column lowering."""
    with jax.named_scope("det_solve"):
        M = jnp.concatenate([A, b[:, None]], axis=1)
        return numerics._gauss_jordan(M, A.shape[0])[:, 0]


@pytest.mark.parametrize("batch", [(5,), (2, 5)], ids=["vmap", "vmap2"])
@pytest.mark.parametrize("r", [2, 3])
@pytest.mark.parametrize("p", [13, 38, 65, 131])
def test_right_hand_sides_match_one_column_solves_bitwise(p, r, batch):
    """Several right-hand sides in one elimination, unblocked and
    panelled: each column has the bits of its own one-column solve."""
    A, _ = _ridge_systems(p, batch, seed=p)
    b = jax.random.normal(jax.random.PRNGKey(p + r), batch + (p, r))
    d = len(batch)
    x = _batched(det_solve, d)(A, b)
    assert x.shape == batch + (p, r)
    for j in range(r):
        np.testing.assert_array_equal(
            _bits(x[..., j]), _bits(_batched(det_solve, d)(A, b[..., j])))


@pytest.mark.parametrize("p", [13, 65, 501])
def test_one_column_lowering_is_unchanged(p):
    """One right-hand side lowers to the same text as before."""
    spec = (jax.ShapeDtypeStruct((2, 5, p, p), jnp.float32),
            jax.ShapeDtypeStruct((2, 5, p), jnp.float32))

    def text(solve):
        def program(A, b):
            return jax.vmap(jax.vmap(solve))(A, b)
        return jax.jit(program).lower(*spec).as_text(debug_info=False)

    assert text(det_solve) == text(_one_column_det_solve)
