"""The Executor protocol — "how iterative steps run" as a first-class,
swappable choice (the SPMD analogue of Ray's task pool).

The paper's thesis (§5): fold fits, tuning trials, and bootstrap
replicates are embarrassingly parallel, so schedule them as concurrent
tasks instead of Python loops.  An Executor maps a fit-closure over a
leading *replicate* axis:

  serial     one compiled program per replicate, strictly in sequence —
             the EconML/Ray-less baseline every benchmark compares to;
  vmap       all replicates stacked and batched into ONE program — the
             single-host translation of Ray's task pool (paper C1/C2);
  shard_map  the replicate axis sharded over the ``data`` mesh axis via
             distributed/sharding.py rules — replicates spread across
             devices, each shard running the vmapped program locally.

``serial`` and ``vmap`` are *bit-identical* per replicate when the
closure is built from the replicate-invariant vocabulary in
``inference/numerics.py`` (tests assert this).  Closures take one pytree
argument whose leaves carry the replicate axis first (PRNG keys,
hyper-parameter values, fold weights, ...) and return a pytree of
arrays.
"""
from __future__ import annotations

import contextlib
import dataclasses
import weakref
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


@runtime_checkable
class Executor(Protocol):
    """Maps ``fn`` over the leading axis of ``xs`` (a pytree).  Extra
    ``*args`` are passed through to every call UN-mapped (replicated) —
    use them for the data tensors so they enter the compiled program as
    arguments, not as baked-in constants XLA will try to fold (a real
    compile-time cost at industrial n)."""

    name: str

    def map(self, fn: Callable[..., Any], xs: Any, *args: Any) -> Any:
        ...


def _leading_dim(xs: Any) -> int:
    leaves = jax.tree_util.tree_leaves(xs)
    if not leaves:
        raise ValueError("executor.map needs at least one array input")
    return leaves[0].shape[0]


def _index(xs: Any, i: int) -> Any:
    return jax.tree_util.tree_map(lambda x: x[i], xs)


# Observability taps (repro.obs): callables invoked with the closure on
# every _JitCache miss — a miss is a fresh jit wrapper, i.e. a compile
# the executor could not amortize.  Empty list (the default) costs one
# falsy check per miss; hooks are installed scoped via jit_miss_hook().
_JIT_MISS_HOOKS: list = []


@contextlib.contextmanager
def jit_miss_hook(cb: Callable[[Any], None]):
    """Scoped registration of a jit-cache-miss observer (the tracer's
    per-closure recompile counter)."""
    _JIT_MISS_HOOKS.append(cb)
    try:
        yield
    finally:
        _JIT_MISS_HOOKS.remove(cb)


def _weak_call(fn):
    """``fn`` called through a weak reference, under ``fn``'s name (the
    jitted program keeps it).  A cached wrapper built on ``fn`` itself
    would pin its own weak key, and the closure, with everything it
    captures, could never be freed."""
    ref = weakref.ref(fn)

    def call(*a):
        return ref()(*a)

    call.__name__ = getattr(fn, "__name__", call.__name__)
    call.__qualname__ = getattr(fn, "__qualname__", call.__qualname__)
    return call


class _JitCache:
    """Per-executor compiled-program reuse: ``map(fn, ...)`` called twice
    with the SAME closure object hits the same jit wrapper (and thus its
    compilation cache) instead of re-tracing.  Weak keys let dead
    closures drop out: the wrapper reaches ``fn`` only weakly."""

    def __init__(self):
        self._cache = weakref.WeakKeyDictionary()

    def get(self, fn, build):
        f = self._cache.get(fn)
        if f is None:
            if _JIT_MISS_HOOKS:
                for hook in tuple(_JIT_MISS_HOOKS):
                    hook(fn)
            f = build(_weak_call(fn))
            self._cache[fn] = f
        return f


@dataclasses.dataclass
class SerialExecutor:
    """Python loop over replicates — one dispatch per replicate, like K
    Ray-less workers.  The runtime baseline for bench_inference."""

    name: str = "serial"
    jit: bool = True

    def __post_init__(self):
        self._jits = _JitCache()

    def map(self, fn, xs, *args):
        f = self._jits.get(fn, jax.jit) if self.jit else fn
        outs = [f(_index(xs, i), *args) for i in range(_leading_dim(xs))]
        return jax.tree_util.tree_map(lambda *ys: jnp.stack(ys), *outs)


@dataclasses.dataclass
class VmapExecutor:
    """All replicates as ONE batched program (the paper's translation of
    the Ray task pool to SPMD).

    ``microbatch`` caps how many replicates are batched per program:
    the (B, k, n, p) weighted-Gram intermediates grow linearly in the
    batch, so at industrial n a full-B program can exceed memory; chunks
    of the same compiled program keep the batching win with bounded
    footprint (bit-identity is preserved — per-replicate numerics are
    batch-size-invariant)."""

    name: str = "vmap"
    microbatch: Optional[int] = None

    def __post_init__(self):
        self._jits = _JitCache()

    def map(self, fn, xs, *args):
        def build(g):
            @jax.jit
            def batched(xs_, *a):
                return jax.vmap(lambda x_: g(x_, *a))(xs_)
            return batched

        f = self._jits.get(fn, build)
        b = _leading_dim(xs)
        c = self.microbatch
        if not c or c >= b:
            return f(xs, *args)
        outs = [f(jax.tree_util.tree_map(lambda x: x[i:i + c], xs), *args)
                for i in range(0, b, c)]
        return jax.tree_util.tree_map(
            lambda *ys: jnp.concatenate(ys, axis=0), *outs)


@dataclasses.dataclass
class ShardMapExecutor:
    """Replicate axis sharded over a mesh axis; each shard runs the
    vmapped program on its local replicates.  The replicate count is
    padded up to a multiple of the mesh axis size (padding replays
    replicate 0 and is dropped from the output)."""

    mesh: Optional[Mesh] = None
    axis: str = "data"
    name: str = "shard_map"

    def __post_init__(self):
        self._jits = _JitCache()

    def _mesh(self) -> Mesh:
        if self.mesh is not None:
            return self.mesh
        return Mesh(np.asarray(jax.devices()), (self.axis,))

    def map(self, fn, xs, *args):
        mesh = self._mesh()
        size = mesh.shape[self.axis]
        b = _leading_dim(xs)
        pad = (-b) % size

        def pad_leaf(x):
            if pad == 0:
                return x
            return jnp.concatenate(
                [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])], axis=0)

        xs_p = jax.tree_util.tree_map(pad_leaf, xs)
        spec = P(self.axis)

        def build(g):
            @jax.jit
            def sharded(xs_, *a):
                # replicate axis sharded; pass-through args replicated
                inner = jax.shard_map(
                    lambda x_, *aa: jax.vmap(lambda e: g(e, *aa))(x_),
                    mesh=mesh,
                    in_specs=(spec,) + tuple(
                        jax.tree_util.tree_map(lambda _: P(), aa_)
                        for aa_ in a),
                    out_specs=spec, check_vma=False)
                return inner(xs_, *a)
            return sharded

        out = self._jits.get(fn, build)(xs_p, *args)
        return jax.tree_util.tree_map(lambda y: y[:b], out)


# Default serial/vmap executors are process-wide singletons: their
# _JitCache (keyed on closure objects) is what turns "call crossfit /
# bootstrap again" into a compile-cache hit instead of a re-trace.
_DEFAULT_EXECUTORS: dict = {}


def make_executor(name, *, mesh: Optional[Mesh] = None,
                  rules=None) -> Executor:
    """Factory.  ``name`` may already be an Executor (passed through).
    For ``shard_map`` the mesh axis defaults to the one the sharding
    rules assign to the logical ``replicate`` axis (falling back to
    ``data``) — the same rule table that shards DML rows."""
    if isinstance(name, (SerialExecutor, VmapExecutor, ShardMapExecutor)):
        return name
    if not isinstance(name, str) and isinstance(name, Executor):
        return name
    if name == "serial":
        return _DEFAULT_EXECUTORS.setdefault("serial", SerialExecutor())
    if name == "vmap":
        return _DEFAULT_EXECUTORS.setdefault("vmap", VmapExecutor())
    if name == "shard_map":
        axis = "data"
        if rules is not None:
            mapped = rules.get("replicate")
            if isinstance(mapped, (tuple, list)):
                mapped = mapped[-1] if mapped else None
            if isinstance(mapped, str):
                axis = mapped
        return ShardMapExecutor(mesh=mesh, axis=axis)
    raise ValueError(f"unknown executor {name!r} "
                     "(expected serial | vmap | shard_map)")
