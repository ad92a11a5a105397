"""Multi-process row-sharded moment reduction — the data-mesh layer.

The paper's deployment story (arXiv 2401.11932) is data parallelism
over a Ray cluster: rows live where they land, the iterative causal
steps reduce locally, and only fixed-size sufficient statistics cross
the wire.  Every estimator here already bottoms out in Gram-shaped
accumulators of at most (S·qL, qR) floats (``repro.core.moments`` /
``repro.kernels.seg_gram``), so the native reproduction is a
``shard_map`` over a ``("hosts", "devices")`` mesh: shard the row
axis, reduce per shard, combine the tiny accumulators — raw data
never moves.

Bit-identity contract
---------------------
Cross-shard float addition is non-associative, so a naive
local-fold + ``psum`` cannot match the single-process chunked
left-fold bit-for-bit.  The certified scheme sidesteps reassociation
entirely:

  ``reduction="ordered"`` (default)   the distributed path IS the
      "whole" strategy of ``blocked_reduce`` with its per-block
      ``lax.map`` sharded over the data mesh.  Rows pad to
      ``row_block``-sized blocks, the BLOCK axis shards across the
      mesh (``in_specs=P(("hosts", "devices"))``), each shard maps
      the SAME unbatched per-block graph over its local blocks, and
      ``out_specs`` reassembles the per-block partials in global
      block order.  An ordinary ``lax.scan`` left-fold OUTSIDE the
      shard_map then replays exactly the addition sequence the
      single-process "whole" strategy runs — and chunked ≡ whole is
      already structural (core.moments).  ``init`` seeds that fold,
      so ``MomentStore.ingest`` inherits its aligned-ingest bitwise
      certificate unchanged.  Extra all-padding blocks (the block
      count rounds up to a multiple of the shard count) contribute
      exactly +0.0 to every accumulator.

  ``reduction="psum"``   the wire-efficient mode: each shard
      left-folds its local partials, then one tree-order ``psum``
      combines the S accumulators.  S-1 adds cross the wire instead
      of nb partial tensors — but the addition order differs from
      the chunked path, so equality is tolerance-grade (float
      reassociation), NOT bitwise.  Use it when bandwidth matters
      more than the certificate.

Activation is context-scoped: ``use_data_mesh(dm)`` makes every
blocked moments entry point (``weighted_gram``, ``fold_gram``,
``iv_gram``, the seg_gram lowerings, store-ingest seeds) route
through ``dist_reduce`` at TRACE time.  ``TaskRuntime(data_mesh=...)``
wraps task closures in this context and extends the downgrade ladder
with a shard_map → single-host rung (runtime.scheduler).

Single-host simulation: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for an 8-way
CPU mesh; ``launch/dist_smoke.py`` exercises the host axis with two
real ``jax.distributed`` processes (best-effort).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import _mk

Array = jax.Array

DATA_AXES: Tuple[str, str] = ("hosts", "devices")


class ShardLostError(RuntimeError):
    """A mesh shard died (or was injected dead) during a distributed
    reduction — the runtime ladder downgrades to single-host, the
    sweep engine isolates the loss to one column."""


@dataclasses.dataclass(frozen=True)
class DataMesh:
    """A row-sharding mesh: rows split across ``hosts × devices``,
    fixed-size Gram accumulators combine across it."""

    mesh: Any
    axis_names: Tuple[str, str] = DATA_AXES
    reduction: str = "ordered"  # "ordered" (bitwise) | "psum" (tolerance)

    @property
    def n_shards(self) -> int:
        s = 1
        for ax in self.axis_names:
            s *= self.mesh.shape[ax]
        return s

    @property
    def label(self) -> str:
        shape = "x".join(str(self.mesh.shape[ax]) for ax in self.axis_names)
        return f"{shape}:{self.reduction}"


def make_data_mesh(n_hosts: int = 0, n_devices: int = 0, *,
                   devices: Optional[Sequence] = None,
                   reduction: str = "ordered") -> DataMesh:
    """Build a ``("hosts", "devices")`` DataMesh.  Defaults: one host
    row per participating process (``jax.process_count()``), all local
    devices spread along the device axis.  Under a single process with
    one device this degrades to a (1, 1) mesh — same code path, no
    parallelism."""
    if reduction not in ("ordered", "psum"):
        raise ValueError(f"unknown reduction {reduction!r} "
                         "(expected ordered | psum)")
    devs = list(devices) if devices is not None else list(jax.devices())
    h = int(n_hosts) or max(1, jax.process_count())
    d = int(n_devices) or max(1, len(devs) // h)
    if len(devs) < h * d:
        raise RuntimeError(
            f"data mesh ({h}, {d}) needs {h * d} devices but only "
            f"{len(devs)} exist (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=<N> before jax init)")
    mesh = _mk((h, d), DATA_AXES, devices=devs[: h * d])
    return DataMesh(mesh=mesh, reduction=reduction)


# -- context-scoped activation (thread-local: job threads must not ----------
# -- leak a mesh into each other's traces) ----------------------------------

_ACTIVE = threading.local()


def current_data_mesh() -> Optional[DataMesh]:
    """The innermost active DataMesh (None outside ``use_data_mesh``).
    Read at TRACE time by ``blocked_reduce`` / ``seg_reduce``."""
    stack = getattr(_ACTIVE, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def use_data_mesh(dm: Optional[DataMesh]):
    """Route every blocked moment reduction traced inside this context
    through ``dist_reduce`` over ``dm``.  ``None`` is a no-op (so call
    sites can pass an optional mesh unconditionally)."""
    if dm is None:
        yield None
        return
    stack = getattr(_ACTIVE, "stack", None)
    if stack is None:
        stack = _ACTIVE.stack = []
    stack.append(dm)
    try:
        yield dm
    finally:
        stack.pop()


# -- deterministic failure injection (tests: lost-shard ladder rung + -------
# -- per-column sweep isolation) --------------------------------------------

_FAIL_BUDGET = [0]


def inject_shard_failure(n: int = 1) -> None:
    """Arm the next ``n`` distributed reductions to raise
    ``ShardLostError`` at trace time — a deterministic stand-in for a
    dead worker.  The budget is global and one-shot per reduction;
    ``inject_shard_failure(0)`` disarms."""
    _FAIL_BUDGET[0] = int(n)


def _maybe_fail() -> None:
    if _FAIL_BUDGET[0] > 0:
        _FAIL_BUDGET[0] -= 1
        raise ShardLostError(
            "injected shard failure (inject_shard_failure)")


def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def dist_reduce(block_fn: Callable[..., Any], arrays: Sequence[Array], *,
                row_block: int, dm: Optional[DataMesh] = None,
                pad_values: Optional[Sequence] = None,
                init: Optional[Any] = None,
                reduction: Optional[str] = None) -> Any:
    """Row-sharded ``blocked_reduce``: split ``row_block``-sized blocks
    of the leading axis across ``dm``'s mesh, evaluate ``block_fn`` per
    block per shard, combine the fixed-size accumulators.

    ``reduction="ordered"`` is bit-identical to the single-process
    chunked/whole strategies at equal ``row_block`` (module docstring);
    ``"psum"`` trades the certificate for one tree-order all-reduce.
    ``block_fn``'s contract is blocked_reduce's: row-additive, zero
    rows contribute exact zeros, ``pad_values`` pins per-array padding
    constants (e.g. -1 fold ids), ``init`` seeds the ordered fold.
    """
    dm = dm if dm is not None else current_data_mesh()
    if dm is None:
        raise ValueError("dist_reduce needs a DataMesh (pass dm= or "
                         "enter use_data_mesh)")
    _maybe_fail()
    arrays = tuple(arrays)
    n = arrays[0].shape[0]
    r = int(row_block)
    if r <= 0:
        raise ValueError("dist_reduce requires row_block > 0")
    tmap = jax.tree_util.tree_map
    S = dm.n_shards
    # block count rounds up to a multiple of the shard count so the
    # block axis splits evenly; the extra blocks are all padding and
    # contribute exactly +0.0 per the block_fn zero-row contract
    nb = -(-n // r)
    nb = -(-nb // S) * S
    pad = nb * r - n
    if pad:
        pv = pad_values or (0,) * len(arrays)
        arrays = tuple(
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=v)
            for a, v in zip(arrays, pv))
    blocks = tuple(a.reshape((nb, r) + a.shape[1:]) for a in arrays)
    spec = P(dm.axis_names)
    mode = reduction or dm.reduction

    if mode == "ordered":
        def shard(*bs):
            # the SAME unbatched per-block graph as the single-process
            # "whole" strategy — lax.map, NOT vmap (core.moments)
            return lax.map(lambda xs: block_fn(*xs), bs)

        parts = _smap(shard, dm.mesh, (spec,) * len(blocks),
                      spec)(*blocks)
        acc0 = (init if init is not None
                else tmap(lambda x: jnp.zeros(x.shape[1:], x.dtype), parts))
        out, _ = lax.scan(lambda acc, g: (tmap(jnp.add, acc, g), None),
                          acc0, parts)
        return out

    if mode != "psum":
        raise ValueError(f"unknown reduction {mode!r} "
                         "(expected ordered | psum)")

    axes = dm.axis_names

    def shard(*bs):
        parts = lax.map(lambda xs: block_fn(*xs), bs)
        zero = tmap(lambda x: jnp.zeros(x.shape[1:], x.dtype), parts)
        local, _ = lax.scan(lambda acc, g: (tmap(jnp.add, acc, g), None),
                            zero, parts)
        return tmap(lambda x: lax.psum(x, axes), local)

    out = _smap(shard, dm.mesh, (spec,) * len(blocks), P())(*blocks)
    return out if init is None else tmap(jnp.add, init, out)
