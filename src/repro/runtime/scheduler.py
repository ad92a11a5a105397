"""The task scheduler: Ray's pool semantics over Executor backends.

``TaskRuntime`` grows PR 1's flat ``Executor.map`` into the scheduling
layer the paper attributes to Ray:

  chunked scheduling   the replicate axis is split into chunks sized by
                       the affine peak-memory model of the lowered
                       closure (runtime.memory) against a per-device
                       budget — ``n_bootstrap=2000`` streams instead of
                       OOMing one giant vmap;
  fault tolerance      each chunk retries down the backend ladder
                       (shard_map → vmap → serial) on failure, the SPMD
                       stand-in for Ray re-executing a lost task on
                       another worker.  Results stay bit-identical:
                       per-replicate numerics are batch-size-invariant
                       and serial ≡ vmap bitwise, so a downgraded chunk
                       computes the same bits the healthy backend would
                       have;
  deterministic order  chunks are dispatched and concatenated in fixed
                       replicate order, whatever backends ran them;
  nested parallelism   ``map_product`` flattens two parallel axes
                       (replicate × fold, trial × fold) into ONE
                       batched program, with the same chunked/fault-
                       tolerant machinery subdividing the product axis
                       when the budget demands — the scheduler, not the
                       caller, decides how much runs at once;
  futures              ``submit``/``call``/``gather`` (runtime.future)
                       express dependent stages — successive-halving
                       rungs, refuter panels — as a task DAG instead of
                       hand-ordered loops.

A ``TaskRuntime`` with no budget, no explicit chunk, and a healthy
backend degenerates to exactly one ``Executor.map`` call, so migrating
callers onto the runtime costs nothing on the happy path.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import weakref
from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.inference.executor import Executor, jit_miss_hook, make_executor
from repro.obs.audit import ChunkAudit
from repro.obs.metrics import default_registry
from repro.obs.trace import Tracer, layer_span, maybe_span
from repro.runtime.future import TaskFuture, TaskGraph, resolve
from repro.runtime.memory import (MemoryModel, compiled_chunk, memory_model,
                                  probe_chunk_cost)

# The fault-tolerance ladder: each backend's failure falls back to the
# next-simpler one.  serial has no fallback — its failure is the task's.
DOWNGRADE: dict = {"shard_map": "vmap", "vmap": "serial", "serial": None}


@dataclasses.dataclass(frozen=True)
class RuntimeEvent:
    """One scheduling decision or recovery, for tests and reports."""

    action: str  # "chunk" | "retry" | "downgrade"
    label: str
    chunk_index: int = -1
    backend: str = ""
    detail: str = ""


class EventLog:
    """Bounded RuntimeEvent record: list-like for readers, ring-buffered
    so a long-lived runtime (thousands of ``map`` calls) cannot grow an
    unbounded host-side list.  ``total`` counts every event ever
    appended; ``since(start_total)`` recovers a suffix recorded from a
    ``total`` checkpoint even after older entries were dropped — the
    drop-safe replacement for ``events[start:]`` slicing.  The tracer is
    the durable record; this log is the cheap always-on tail."""

    def __init__(self, maxlen: int = 512):
        self._buf: "collections.deque[RuntimeEvent]" = collections.deque(
            maxlen=maxlen
        )
        self._total = 0

    def append(self, event: RuntimeEvent) -> None:
        self._buf.append(event)
        self._total += 1

    @property
    def total(self) -> int:
        return self._total

    @property
    def dropped(self) -> int:
        return self._total - len(self._buf)

    def since(self, start_total: int) -> Tuple[RuntimeEvent, ...]:
        """Events appended at or after the ``total`` checkpoint
        ``start_total`` that are still buffered."""
        skip = max(0, start_total - self.dropped)
        return tuple(self._buf)[skip:]

    def clear(self) -> None:
        self._buf.clear()
        self._total = 0

    def __len__(self) -> int:
        return len(self._buf)

    def __iter__(self) -> Iterator[RuntimeEvent]:
        return iter(tuple(self._buf))

    def __getitem__(self, ix):
        return tuple(self._buf)[ix]


def _leading_dim(xs: Any) -> int:
    leaves = jax.tree_util.tree_leaves(xs)
    if not leaves:
        raise ValueError("runtime.map needs at least one array input")
    return leaves[0].shape[0]


def _slice(xs: Any, lo: int, hi: int) -> Any:
    return jax.tree_util.tree_map(lambda x: x[lo:hi], xs)


def _empty_like_mapped(fn, xs: Any, args: Tuple[Any, ...]) -> Any:
    """Zero-replicate output: (0, ...) stacked leaves with the shapes
    and dtypes one application of ``fn`` would produce."""
    elem = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), xs
    )
    arg_spec = tuple(
        jax.tree_util.tree_map(
            lambda a: (
                jax.ShapeDtypeStruct(a.shape, a.dtype) if hasattr(a, "shape") else a
            ),
            arg,
        )
        for arg in args
    )
    out = jax.eval_shape(fn, elem, *arg_spec)
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros((0,) + tuple(s.shape), s.dtype), out
    )


class TaskRuntime:
    """Memory-aware, fault-tolerant scheduler over Executor backends.

    Parameters
    ----------
    executor       backend name (serial | vmap | shard_map) or Executor
                   instance — the *preferred* backend; failures walk the
                   DOWNGRADE ladder from there.
    memory_budget  bytes/device the batched program may peak at; 0
                   disables the memory model (one chunk).
    chunk          explicit replicate chunk size; 0 defers to the
                   memory model (CausalConfig.runtime_chunk).
    max_retries    extra attempts a chunk gets after its first failure
                   (each attempt moves one rung down the ladder).
    data_mesh      optional runtime.distributed.DataMesh: task closures
                   trace with the mesh active, so every blocked moment
                   reduction inside them row-shards across
                   ("hosts", "devices") — bitwise the single-host
                   result in "ordered" mode.  The ladder gains a
                   shard_map → single-host rung on top: a lost shard
                   (ShardLostError or any mesh failure) retries the
                   SAME chunk without the mesh, same bits.
    tracer         optional repro.obs.Tracer: spans around map / chunk /
                   DAG-node execution (block_until_ready-honest), chunk
                   latency histograms, downgrade/retry/jit-miss
                   counters, and the predicted-vs-measured cost audit
                   joining each chunk to its hlo_cost probes.  None (the
                   default) puts the ``runtime.map`` / ``runtime.plan``
                   / ``runtime.chunk`` spans on the process tracer and
                   forces nothing — the same compiled programs run
                   either way.
    events_maxlen  ring-buffer capacity of the always-on RuntimeEvent
                   tail (EventLog; the tracer is the unbounded record).
    """

    # fn -> fused (outer, inner) wrapper, weak so dead closures drop out
    # (same pattern as the executors' _JitCache: the executor keys its
    # compiled cache on the closure object, so the wrapper must be
    # stable per fn).
    _PRODUCT_FNS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

    def __init__(
        self,
        executor="vmap",
        *,
        memory_budget: int = 0,
        chunk: int = 0,
        max_retries: int = 2,
        mesh=None,
        rules=None,
        data_mesh=None,
        tracer: Optional[Tracer] = None,
        events_maxlen: int = 512,
    ):
        self._primary = make_executor(executor, mesh=mesh, rules=rules)
        self._mesh = mesh
        self._rules = rules
        self.data_mesh = data_mesh
        self.memory_budget = int(memory_budget)
        self.chunk = int(chunk)
        self.max_retries = int(max_retries)
        self.tracer = tracer
        self.events = EventLog(maxlen=events_maxlen)
        self._graph = TaskGraph()
        # fn -> mesh-activating wrapper, per runtime: the executor jit
        # cache keys on the closure OBJECT, so mesh and plain traces of
        # the same fn must go through distinct stable closures
        self._mesh_fns: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

    def _emit(self, event: RuntimeEvent) -> None:
        """Record one scheduling decision: always into the bounded
        EventLog and as a ``runtime.events.<action>`` counter on the
        process registry (so a retry or downgrade deep inside an
        estimator is visible to whoever drives it); when tracing, also
        as an instant marker + counter on the tracer."""
        self.events.append(event)
        default_registry().counter(f"runtime.events.{event.action}").inc()
        tr = self.tracer
        if tr is not None:
            tr.instant(
                f"runtime.event.{event.action}",
                cat="runtime",
                label=event.label,
                chunk_index=event.chunk_index,
                backend=event.backend,
                detail=event.detail,
            )
            tr.metrics.counter(f"runtime.events.{event.action}").inc()

    # -- identity -------------------------------------------------------
    @property
    def name(self) -> str:
        return self._primary.name

    # -- backend ladder -------------------------------------------------
    def _ladder(self) -> Tuple[Executor, ...]:
        chain: List[Executor] = [self._primary]
        nxt = DOWNGRADE.get(self._primary.name, "vmap")
        while nxt is not None:
            chain.append(make_executor(nxt, mesh=self._mesh, rules=self._rules))
            nxt = DOWNGRADE.get(nxt)
        # dedupe by backend name, keeping first occurrence
        seen, out = set(), []
        for exe in chain:
            if exe.name not in seen:
                seen.add(exe.name)
                out.append(exe)
        return tuple(out)

    def _mesh_variant(self, fn):
        """A stable per-(runtime, fn) closure whose trace runs with the
        data mesh active — so blocked moments inside ``fn`` row-shard
        (runtime.distributed), and the mesh trace caches separately
        from the plain one."""
        wrapped = self._mesh_fns.get(fn)
        if wrapped is None:
            fn_ref = weakref.ref(fn)
            dm = self.data_mesh

            def wrapped(*a, **kw):
                from repro.runtime.distributed import use_data_mesh

                with use_data_mesh(dm):
                    return fn_ref()(*a, **kw)

            self._mesh_fns[fn] = wrapped
        return wrapped

    def _jit_miss_scope(self, label: str):
        """While tracing, count executor jit-cache misses (fresh compiled
        wrappers) per closure under ``jit_cache_miss[...]`` counters."""
        tr = self.tracer
        if tr is None:
            return contextlib.nullcontext()

        def on_miss(fn):
            name = getattr(fn, "__name__", type(fn).__name__)
            tr.metrics.counter(f"jit_cache_miss[{label or name}]").inc()

        return jit_miss_hook(on_miss)

    def _run_chunk(
        self,
        fn,
        xs_c: Any,
        args: Tuple[Any, ...],
        label: str,
        index: int,
        model: Optional[MemoryModel] = None,
    ) -> Any:
        err: Optional[BaseException] = None
        # the attempt plan: an optional data-mesh rung on the primary
        # backend first (lost shards fall back to the SAME chunk
        # single-host, same bits), then the plain backend ladder
        plans: List[Tuple[Executor, Any, str]] = []
        if self.data_mesh is not None:
            plans.append(
                (
                    self._primary,
                    self._mesh_variant(fn),
                    f"data_mesh[{self.data_mesh.label}]:{self._primary.name}",
                )
            )
        plans.extend((exe, fn, exe.name) for exe in self._ladder())
        for attempt, (exe, run_fn, rung) in enumerate(plans):
            if attempt > self.max_retries:
                break
            if attempt:
                self._emit(
                    RuntimeEvent("downgrade", label, index, rung, str(err))
                )
            try:
                tr = self.tracer
                if tr is None:
                    with layer_span(None, "runtime.chunk", label=label,
                                    chunk_index=index,
                                    chunk_size=_leading_dim(xs_c),
                                    backend=exe.name):
                        return self._exec(exe, run_fn, xs_c, args)
                return self._run_chunk_traced(
                    tr, exe, run_fn, xs_c, args, label, index, model
                )
            except Exception as e:  # noqa: BLE001 — the ladder handles it
                err = e
                # a re-attempt is coming iff the plan has a lower rung
                # left AND the retry budget allows it — that re-attempt
                # is a distinct "retry" event carrying the trigger
                if attempt < self.max_retries and attempt + 1 < len(plans):
                    self._emit(
                        RuntimeEvent("retry", label, index, rung, str(e))
                    )
        assert err is not None
        raise err

    @staticmethod
    def _exec(exe: Executor, fn, xs_c: Any, args: Tuple[Any, ...]) -> Any:
        """``exe.map``, or — on the plain vmap rung — the program the
        memory-model probe already compiled for exactly this chunk (the
        same vmapped computation, so no second compile)."""
        if exe.name == "vmap" and not getattr(exe, "microbatch", None):
            pre = compiled_chunk(fn, xs_c, args)
            if pre is not None:
                return pre(xs_c, *args)
        return exe.map(fn, xs_c, *args)

    def _run_chunk_traced(
        self, tr, exe, fn, xs_c, args, label: str, index: int,
        model: Optional[MemoryModel],
    ) -> Any:
        """One chunk attempt under an open span: duration is
        block_until_ready-honest, latency feeds the chunk histogram,
        and — when the memory model sized this map — the chunk joins
        the predicted-vs-measured cost audit."""
        csize = _leading_dim(xs_c)
        with tr.span(
            "runtime.chunk",
            cat="runtime",
            label=label,
            chunk_index=index,
            chunk_size=csize,
            backend=exe.name,
        ) as sp:
            with self._jit_miss_scope(label):
                out = self._exec(exe, fn, xs_c, args)
            tr.sync(out)
        tr.metrics.counter("runtime.chunks").inc()
        tr.metrics.histogram("runtime.chunk_seconds").observe(sp.duration_s)
        if model is not None:
            cost = probe_chunk_cost(fn, xs_c, args, csize)
            if cost is not None:
                tr.audit.record(
                    ChunkAudit(
                        label=label,
                        chunk_index=index,
                        chunk_size=csize,
                        predicted_peak_bytes=model.peak(csize),
                        probed_peak_bytes=cost.peak_temp_bytes,
                        flops=cost.flops,
                        hbm_bytes=cost.hbm_bytes,
                        measured_s=sp.duration_s,
                    )
                )
        return out

    # -- chunk sizing ---------------------------------------------------
    def plan_chunk(
        self, fn, xs: Any, args: Tuple[Any, ...], b: int
    ) -> Tuple[int, Optional[MemoryModel]]:
        """(chunk size, memory model) the scheduler would use for this
        map — exposed so benches can report predicted peaks."""
        if self.chunk:
            return max(1, min(self.chunk, b)), None
        if self.memory_budget <= 0 or b <= 1:
            return b, None
        model = memory_model(fn, xs, args, b)
        if model is None:
            return b, None
        return model.max_chunk(self.memory_budget, b), model

    # -- the map primitive ----------------------------------------------
    def map(self, fn: Callable[..., Any], xs: Any, *args: Any, label: str = "") -> Any:
        """Map ``fn`` over the leading replicate axis of ``xs`` with
        chunked, fault-tolerant scheduling.  Results are ordered by
        replicate index regardless of chunking or downgrades."""
        b = _leading_dim(xs)
        if b == 0:
            return _empty_like_mapped(fn, xs, args)
        tr = self.tracer
        tag = f"[{label}]" if label else ""
        with layer_span(
            tr, "runtime.map", cat="runtime", label=label, b=b,
            backend=self._primary.name,
        ) as sp:
            probes = default_registry().counter("runtime.probe_compiles")
            probes0 = probes.value
            with layer_span(tr, "runtime.plan", cat="runtime",
                            label=label) as ps:
                chunk, model = self.plan_chunk(fn, xs, args, b)
                ps.attrs.update(chunk=chunk,
                                probes_compiled=probes.value - probes0)
            sp.attrs["chunk"] = chunk
            if model is not None:
                default_registry().gauge(f"runtime.chunk_size{tag}").set(chunk)
            if tr is not None and model is not None:
                tr.metrics.gauge(f"runtime.chunk_size{tag}").set(chunk)
                tr.metrics.gauge(f"runtime.predicted_peak_bytes{tag}").set(
                    model.peak(chunk)
                )
            if chunk >= b:
                return self._run_chunk(fn, xs, args, label, 0, model)
            self._emit(
                RuntimeEvent(
                    "chunk", label, -1, self._primary.name, f"b={b} chunk={chunk}"
                )
            )
            outs = [
                self._run_chunk(
                    fn, _slice(xs, lo, min(lo + chunk, b)), args, label, i, model
                )
                for i, lo in enumerate(range(0, b, chunk))
            ]
            return jax.tree_util.tree_map(
                lambda *ys: jnp.concatenate(ys, axis=0), *outs
            )

    # -- nested parallelism ---------------------------------------------
    def map_product(
        self,
        fn: Callable[..., Any],
        xs_outer: Any,
        xs_inner: Any,
        *args: Any,
        label: str = "",
    ) -> Any:
        """One batched program for two parallel axes: ``fn(xo, xi,
        *args)`` over the (b_outer × b_inner) product, flattened onto a
        single replicate axis so chunking/fault-tolerance subdivide the
        *product* (the scheduler's choice), then reshaped back to
        (b_outer, b_inner, ...)."""
        bo = _leading_dim(xs_outer)
        bi = _leading_dim(xs_inner)
        fused = TaskRuntime._PRODUCT_FNS.get(fn)
        if fused is None:
            # the wrapper holds only a weakref to fn: a strong capture
            # would pin the WeakKeyDictionary key alive through its own
            # value, making every entry immortal.  fn is alive for the
            # duration of any call that passes it in.
            fn_ref = weakref.ref(fn)

            def fused(pair, *a):
                return fn_ref()(pair["outer"], pair["inner"], *a)

            TaskRuntime._PRODUCT_FNS[fn] = fused
        rep = jax.tree_util.tree_map(lambda x: jnp.repeat(x, bi, axis=0), xs_outer)
        til = jax.tree_util.tree_map(
            lambda x: jnp.tile(x, (bo,) + (1,) * (x.ndim - 1)), xs_inner
        )
        flat = self.map(
            fused, {"outer": rep, "inner": til}, *args, label=label or "map_product"
        )
        return jax.tree_util.tree_map(
            lambda y: y.reshape((bo, bi) + y.shape[1:]), flat
        )

    # -- futures API -----------------------------------------------------
    def submit(
        self,
        fn: Callable[..., Any],
        xs: Any,
        *args: Any,
        deps: Sequence[TaskFuture] = (),
        label: str = "",
    ) -> TaskFuture:
        """Deferred ``map``: returns a TaskFuture immediately.  ``xs`` /
        ``args`` may contain TaskFutures — resolved when gathered."""
        return self._graph.submit("map", fn, xs, args, deps, label)

    def call(
        self,
        fn: Callable[..., Any],
        *args: Any,
        deps: Sequence[TaskFuture] = (),
        label: str = "",
    ) -> TaskFuture:
        """Deferred host call — the glue nodes between map stages
        (survivor selection, reductions)."""
        return self._graph.submit("call", fn, None, args, deps, label)

    def gather(self, futures):
        """Execute the DAG below ``futures`` (deterministic topological
        order) and return their results, preserving structure.  With a
        tracer, every executed map node gets a ``dag.task`` span (its
        chunk spans nest inside)."""
        single = isinstance(futures, TaskFuture)
        targets = [futures] if single else list(futures)

        def run_map(f: TaskFuture):
            with maybe_span(
                self.tracer, "dag.task", cat="dag",
                label=f.label or f"task{f.task_id}", task_id=f.task_id,
            ):
                return self.map(
                    f.fn, resolve(f.xs), *resolve(f.args), label=f.label
                )

        self._graph.execute(targets, run_map)
        out = [t.result() for t in targets]
        return out[0] if single else out


def as_runtime(
    executor,
    *,
    mesh=None,
    rules=None,
    data_mesh=None,
    memory_budget: int = 0,
    chunk: int = 0,
    max_retries: int = 2,
    tracer: Optional[Tracer] = None,
) -> TaskRuntime:
    """Coerce an executor name / Executor / TaskRuntime into a
    TaskRuntime — the adapter every migrated caller goes through.  A
    TaskRuntime passes through untouched (it keeps its own tracer and
    data mesh); ``tracer`` / ``data_mesh`` attach to freshly-built
    runtimes only."""
    if isinstance(executor, TaskRuntime):
        return executor
    return TaskRuntime(
        executor,
        mesh=mesh,
        rules=rules,
        data_mesh=data_mesh,
        memory_budget=memory_budget,
        chunk=chunk,
        max_retries=max_retries,
        tracer=tracer,
    )
