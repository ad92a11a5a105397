"""repro.runtime: futures/DAG semantics, memory-aware chunked
scheduling, fault-tolerant backend downgrade (bitwise-deterministic),
and nested parallelism — plus the executor/runtime edge cases: zero-
length replicate axis, chunk sizes that don't divide B, and retry-
downgrade runs that must be bit-identical to the no-failure run."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import CausalConfig
from repro.core.dml import DML
from repro.data.causal_dgp import make_causal_data
from repro.inference.bootstrap import (dml_bootstrap,
                                       make_dml_replicate_fn,
                                       replicate_keys)
from repro.inference.executor import VmapExecutor
from repro.runtime import (DOWNGRADE, MemoryModel, TaskRuntime, as_runtime,
                           memory_model)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


def _double(x, c):
    return {"y": x * 2.0 + c, "s": x.sum()}


_XS = jnp.arange(14, dtype=jnp.float32).reshape(7, 2)
_C = jnp.float32(1.0)


# ---------------------------------------------------------------------------
# Futures / task graph
# ---------------------------------------------------------------------------

def test_submit_gather_chain():
    rt = TaskRuntime("vmap")
    a = rt.submit(_double, _XS, _C, label="a")
    b = rt.call(lambda o: o["y"][:3], a, label="slice")
    c = rt.submit(_double, b, jnp.float32(0.0), label="c")
    out = rt.gather(c)
    np.testing.assert_array_equal(
        np.asarray(out["y"]), np.asarray((_XS[:3] * 2 + 1) * 2))


def test_gather_many_preserves_structure():
    rt = TaskRuntime("vmap")
    a = rt.submit(_double, _XS, _C)
    b = rt.call(lambda o: float(o["s"].sum()), a)
    ra, rb = rt.gather([a, b])
    assert ra["y"].shape == (7, 2)
    assert rb == pytest.approx(float(_XS.sum()))  # Σ per-replicate sums


def test_result_before_gather_raises():
    rt = TaskRuntime("vmap")
    a = rt.submit(_double, _XS, _C)
    with pytest.raises(RuntimeError, match="gather"):
        a.result()


def test_cycle_detection():
    rt = TaskRuntime("vmap")
    a = rt.call(lambda v: v, 1)
    b = rt.call(lambda v: v, a)
    a.deps = (b,)  # forge a cycle
    with pytest.raises(ValueError, match="cycle"):
        rt.gather(b)


def test_gather_is_idempotent():
    rt = TaskRuntime("vmap")
    calls = []
    a = rt.call(lambda: calls.append(1) or 42)
    assert rt.gather(a) == 42
    assert rt.gather(a) == 42
    assert len(calls) == 1  # executed once, replayed from the handle


# ---------------------------------------------------------------------------
# Chunked scheduling
# ---------------------------------------------------------------------------

def test_chunk_not_dividing_axis_is_bitwise():
    full = TaskRuntime("vmap").map(_double, _XS, _C)
    for chunk in (1, 2, 3, 5, 7, 100):
        out = TaskRuntime("vmap", chunk=chunk).map(_double, _XS, _C)
        np.testing.assert_array_equal(np.asarray(full["y"]),
                                      np.asarray(out["y"]))
        np.testing.assert_array_equal(np.asarray(full["s"]),
                                      np.asarray(out["s"]))


def test_zero_length_replicate_axis():
    out = TaskRuntime("vmap").map(_double, _XS[:0], _C)
    assert out["y"].shape == (0, 2)
    assert out["s"].shape == (0,)
    assert out["y"].dtype == jnp.float32


def test_zero_length_axis_serial_backend():
    out = TaskRuntime("serial").map(_double, _XS[:0], _C)
    assert out["y"].shape == (0, 2)


def test_scalar_passthrough_args_survive_budget_and_empty_axis():
    """Executors accept python-scalar pass-through args (jit bakes them
    in); the memory probe and the zero-replicate path must too."""
    full = TaskRuntime("vmap").map(_double, _XS, 0.5)
    budgeted = TaskRuntime("vmap", memory_budget=1 << 20)
    out = budgeted.map(_double, _XS, 0.5)
    np.testing.assert_array_equal(np.asarray(full["y"]), np.asarray(out["y"]))
    empty = TaskRuntime("vmap").map(_double, _XS[:0], 0.5)
    assert empty["y"].shape == (0, 2)


def test_memory_model_and_budget_chunking():
    # closure with a per-replicate (m, m) temp: slope ~ m*m*4 bytes
    m = 64

    def outer(v, base):
        # tanh blocks XLA's algebraic simplifier from collapsing the
        # (m, m) outer-product temp the test is sizing
        return jnp.tanh(v[:, None] * v[None, :] + base).sum()

    xs = jnp.ones((16, m), jnp.float32)
    base = jnp.zeros((m, m), jnp.float32)
    model = memory_model(outer, xs, (base,), 16)
    assert model is not None
    per_rep = m * m * 4
    assert model.slope >= per_rep  # at least the outer-product temp
    # budget for ~4 replicates must chunk below 16 and still be exact
    budget = int(model.base + 4 * model.slope)
    rt = TaskRuntime("vmap", memory_budget=budget)
    chunk, _ = rt.plan_chunk(outer, xs, (base,), 16)
    assert 1 <= chunk <= 4
    out = rt.map(outer, xs, base)
    ref = TaskRuntime("vmap").map(outer, xs, base)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert any(e.action == "chunk" for e in rt.events)


def test_probed_chunk_size_is_not_compiled_twice():
    """A chunk whose size a memory-model probe compiled runs through
    that program: the vmap executor builds no jit of its own, and the
    result is bitwise the unchunked map."""
    from repro.inference.executor import jit_miss_hook
    m = 64

    def outer(v, base):
        return jnp.tanh(v[:, None] * v[None, :] + base).sum()

    xs = jnp.ones((16, m), jnp.float32)
    base = jnp.zeros((m, m), jnp.float32)
    model = memory_model(outer, xs, (base,), 16)
    rt = TaskRuntime("vmap", memory_budget=int(model.base + 2.5 * model.slope))
    assert rt.plan_chunk(outer, xs, (base,), 16)[0] == 2  # a probed size
    misses = []
    with jit_miss_hook(misses.append):
        out = rt.map(outer, xs, base)
    assert misses == []
    ref = TaskRuntime("vmap").map(outer, xs, base)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_max_chunk_floors_at_one():
    model = MemoryModel(base=0.0, slope=1000.0)
    assert model.max_chunk(1, 8) == 1  # one replicate must always run


def test_explicit_chunk_overrides_budget():
    rt = TaskRuntime("vmap", memory_budget=1, chunk=5)
    chunk, model = rt.plan_chunk(_double, _XS, (_C,), 7)
    assert chunk == 5 and model is None


# ---------------------------------------------------------------------------
# Fault tolerance: retry with backend downgrade
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FailingExecutor(VmapExecutor):
    """Backend that dies on its first ``fail_first`` map calls — the
    stand-in for a lost Ray worker."""

    name: str = "failing"
    fail_first: int = 10 ** 9
    calls: int = 0

    def map(self, fn, xs, *args):
        self.calls += 1
        if self.calls <= self.fail_first:
            raise RuntimeError("synthetic worker loss")
        return super().map(fn, xs, *args)


def test_downgrade_result_bitwise_equals_healthy_run():
    healthy = TaskRuntime("vmap", chunk=3).map(_double, _XS, _C)
    rt = TaskRuntime(FailingExecutor(), chunk=3)
    out = rt.map(_double, _XS, _C)
    np.testing.assert_array_equal(np.asarray(healthy["y"]),
                                  np.asarray(out["y"]))
    downs = [e for e in rt.events if e.action == "downgrade"]
    assert len(downs) == 3  # every chunk fell back
    assert all(e.backend == "vmap" for e in downs)


def test_partial_failure_mid_run_is_bitwise():
    """Only the FIRST chunk loses its worker; later chunks run on the
    primary.  The concatenated result must still equal the no-failure
    run bitwise (deterministic replicate order)."""
    healthy = TaskRuntime("vmap", chunk=3).map(_double, _XS, _C)
    flaky = FailingExecutor(fail_first=1)
    rt = TaskRuntime(flaky, chunk=3)
    out = rt.map(_double, _XS, _C)
    np.testing.assert_array_equal(np.asarray(healthy["y"]),
                                  np.asarray(out["y"]))
    assert sum(e.action == "downgrade" for e in rt.events) == 1


def test_retry_events_carry_triggering_exception():
    """Every re-attempt is a distinct "retry" event recording the
    backend that failed and the exception that triggered the fallback
    (satellite of the observability PR: recoveries must be auditable)."""
    rt = TaskRuntime(FailingExecutor(), chunk=3)
    rt.map(_double, _XS, _C)
    retries = [e for e in rt.events if e.action == "retry"]
    downs = [e for e in rt.events if e.action == "downgrade"]
    assert len(retries) == 3  # one per failed chunk attempt
    assert len(retries) == len(downs)  # each retry produced a downgrade
    assert all(e.backend == "failing" for e in retries)
    assert all("synthetic worker loss" in e.detail for e in retries)
    assert [e.chunk_index for e in retries] == [0, 1, 2]


def test_exhausted_ladder_emits_no_retry_event():
    """With no retry budget there is no re-attempt, hence no "retry"
    event — the failure propagates instead."""
    rt = TaskRuntime(FailingExecutor(), max_retries=0)
    with pytest.raises(RuntimeError, match="synthetic"):
        rt.map(_double, _XS, _C)
    assert not [e for e in rt.events if e.action == "retry"]


def test_exhausted_ladder_reraises():
    rt = TaskRuntime(FailingExecutor(), max_retries=0)
    with pytest.raises(RuntimeError, match="synthetic"):
        rt.map(_double, _XS, _C)


def test_downgrade_table_is_a_ladder():
    assert DOWNGRADE["shard_map"] == "vmap"
    assert DOWNGRADE["vmap"] == "serial"
    assert DOWNGRADE["serial"] is None


# ---------------------------------------------------------------------------
# Nested parallelism
# ---------------------------------------------------------------------------

def test_map_product_matches_nested_loops():
    def cell(xo, xi, c):
        return xo * xi + c

    xo = jnp.arange(3, dtype=jnp.float32) + 1
    xi = jnp.arange(4, dtype=jnp.float32)
    out = TaskRuntime("vmap").map_product(cell, xo, xi, _C)
    ref = xo[:, None] * xi[None, :] + _C
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_map_product_chunked_bitwise():
    def cell(xo, xi, c):
        return {"v": xo["a"] * xi + c}

    xo = {"a": jnp.arange(5, dtype=jnp.float32)}
    xi = jnp.arange(6, dtype=jnp.float32)
    full = TaskRuntime("vmap").map_product(cell, xo, xi, _C)
    chunked = TaskRuntime("vmap", chunk=7).map_product(cell, xo, xi, _C)
    np.testing.assert_array_equal(np.asarray(full["v"]),
                                  np.asarray(chunked["v"]))
    assert chunked["v"].shape == (5, 6)


def test_map_product_empty_axis():
    def cell(xo, xi):
        return xo * xi

    out = TaskRuntime("vmap").map_product(
        cell, jnp.zeros((0,), jnp.float32), jnp.arange(4.0))
    assert out.shape == (0, 4)


def test_map_product_empty_inner_axis():
    """Zero-length INNER axis: the flattened product axis is empty, so
    the zero-replicate path must reshape back to (b_outer, 0, ...)."""
    def cell(xo, xi):
        return {"v": xo * xi, "s": xo + xi}

    out = TaskRuntime("vmap").map_product(
        cell, jnp.arange(3.0), jnp.zeros((0,), jnp.float32))
    assert out["v"].shape == (3, 0)
    assert out["s"].shape == (3, 0)
    assert out["v"].dtype == jnp.float32


def test_map_product_both_axes_empty():
    def cell(xo, xi):
        return xo * xi

    out = TaskRuntime("vmap").map_product(
        cell, jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.float32))
    assert out.shape == (0, 0)


# ---------------------------------------------------------------------------
# Integration: bootstrap replicates through the runtime
# ---------------------------------------------------------------------------

# the canonical shapes of test_inference.py, where the replicate-
# invariance contract (serial == vmap bitwise) is asserted to hold —
# chunked scheduling inherits exactly that contract, chunk by chunk
_N, _P, _K = 3000, 8, 4


@pytest.fixture(scope="module")
def ctx(key):
    d = make_causal_data(jax.random.PRNGKey(42), _N, _P, effect=1.5)
    est = DML(CausalConfig(n_folds=_K))
    return est.fit(d.y, d.t, d.X, key=key).fit_ctx


def _boot(ctx, **kw):
    return dml_bootstrap(
        ctx.nuis_y, ctx.nuis_t, n_folds=_K, XW=ctx.XW, y=ctx.y, t=ctx.t,
        phi=ctx.phi, key=jax.random.PRNGKey(11), n_replicates=7, **kw)


def test_bootstrap_chunked_bitwise(ctx):
    full = _boot(ctx, executor="vmap")
    chunked = _boot(ctx, executor="vmap", chunk=3)
    np.testing.assert_array_equal(np.asarray(full.replicates),
                                  np.asarray(chunked.replicates))


def test_bootstrap_downgrade_bitwise(ctx):
    full = _boot(ctx, executor="vmap", chunk=3)
    flaky = _boot(ctx, executor=FailingExecutor(fail_first=1), chunk=3)
    np.testing.assert_array_equal(np.asarray(full.replicates),
                                  np.asarray(flaky.replicates))


def test_bootstrap_memory_budget_chunks_and_is_exact(ctx):
    full = _boot(ctx, executor="vmap")
    # ~2-replicate budget from the probed model, forced through the
    # public path by passing the budget into dml_bootstrap
    fn = make_dml_replicate_fn(ctx.nuis_y, ctx.nuis_t, 3)
    keys = replicate_keys(jax.random.PRNGKey(11), 7)
    model = memory_model(fn, keys, (ctx.XW, ctx.y, ctx.t, ctx.phi), 7)
    assert model is not None and model.slope > 0
    budget = int(model.base + 2.5 * model.slope)
    small = _boot(ctx, executor="vmap", memory_budget=budget)
    np.testing.assert_array_equal(np.asarray(full.replicates),
                                  np.asarray(small.replicates))


def test_as_runtime_passthrough():
    rt = TaskRuntime("serial")
    assert as_runtime(rt) is rt
    assert as_runtime("vmap").name == "vmap"
    assert TaskRuntime("serial").name == "serial"
