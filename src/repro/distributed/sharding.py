"""Logical-axis sharding: the single place that decides how tensors map
onto the production mesh.

Modules declare parameters as :class:`ParamDef` schemas with *logical*
axis names ("embed", "heads", "ff", "experts", ...).  ``ShardingRules``
translate logical names to mesh axes; the same schema therefore serves
1-device smoke tests and the 512-chip multi-pod dry-run unchanged.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declarative parameter: shape + logical axes + initializer."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | scaled | embed
    scale: Optional[float] = None
    dtype: Any = None  # filled from ModelConfig.param_dtype if None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of mesh axes, or None)."""

    rules: Tuple[Tuple[str, Any], ...]

    def get(self, name: Optional[str]):
        if name is None:
            return None
        for k, v in self.rules:
            if k == name:
                return v
        return None


def default_rules(*, fsdp: bool = True, sequence_parallel: bool = False,
                  multi_pod: bool = False, shard_kv_seq: bool = False,
                  fold_axis: Optional[str] = None) -> ShardingRules:
    """Production rules for the (pod, data, model) mesh.

    - batch over ("pod","data") — DP across pods and the data axis.
    - TP dims (heads/ff/vocab/experts) over "model".
    - fsdp shards the 'embed' dim of weights over "data" (+"pod") — ZeRO-3.
    """
    dp: Any = ("pod", "data") if multi_pod else "data"
    weight_dp = dp if fsdp else None
    r = [
        ("batch", dp),
        ("vocab", "model"),
        ("heads", "model"),
        ("kv_heads", "model"),
        ("ff", "model"),
        ("experts", dp),
        ("expert_embed", None),
        ("expert_ff", "model"),
        ("embed", weight_dp),
        ("embed_act", None),   # activations' d_model dim stays unsharded
        ("seq", "model" if sequence_parallel else None),
        ("attn_seq", None),    # q's seq dim inside attention (cells.py may
                               # map it to "model" when heads don't divide TP)
        ("logits_seq", None),  # logits' seq dim (vocab claims "model")
        ("kv_seq", dp if shard_kv_seq else None),
        ("head_dim", None),
        ("state", None),
        ("layers", None),
        ("fold", fold_axis),
        ("qk_lora", None),
        ("inner", "model"),    # mamba/rwkv expanded inner dim
        ("rows", dp),          # causal-data rows (DML engine); inside the
                               # moments engine each row block is
                               # re-constrained on this axis
        ("row_block", None),   # the block index of core.moments blocked
                               # ("whole"-strategy) partials — sequential
                               # reduction order, never sharded
        ("replicate", dp),     # bootstrap/tuning replicate axis
                               # (repro.inference ShardMapExecutor)
    ]
    return ShardingRules(rules=tuple(r))


def logical_to_spec(axes: Sequence[Optional[str]], rules: ShardingRules,
                    mesh: Optional[Mesh] = None) -> P:
    """Translate logical axes to a PartitionSpec, dropping mesh axes that
    do not exist on ``mesh`` (lets one rule set serve all mesh shapes).
    A mesh axis may appear only once in a spec; later logical axes that
    map to an already-used mesh axis fall back to replicated (e.g. under
    sequence parallelism 'seq' claims "model" before 'vocab' would)."""
    names = set(mesh.axis_names) if mesh is not None else None
    used = set()

    def ok(ax):
        return (names is None or ax in names) and ax not in used

    out = []
    for a in axes:
        m = rules.get(a)
        if m is None:
            out.append(None)
        elif isinstance(m, (tuple, list)):
            kept = tuple(x for x in m if ok(x))
            used.update(kept)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            if ok(m):
                used.add(m)
                out.append(m)
            else:
                out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# Schema traversal
# ---------------------------------------------------------------------------

def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def _map_schema(fn: Callable[[str, ParamDef], Any], schema, path: str = ""):
    if _is_def(schema):
        return fn(path, schema)
    if isinstance(schema, Mapping):
        return {k: _map_schema(fn, v, f"{path}/{k}") for k, v in schema.items()}
    raise TypeError(f"bad schema node at {path}: {type(schema)}")


def _path_key(key: jax.Array, path: str) -> jax.Array:
    h = int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "big")
    return jax.random.fold_in(key, h)


def init_params(key: jax.Array, schema, param_dtype=jnp.float32):
    """Materialize a schema into a pytree of initialized arrays."""

    def make(path: str, d: ParamDef):
        dtype = d.dtype or param_dtype
        k = _path_key(key, path)
        if d.init == "zeros":
            return jnp.zeros(d.shape, dtype)
        if d.init == "ones":
            return jnp.ones(d.shape, dtype)
        fan_in = d.shape[0] if len(d.shape) else 1
        if d.init == "embed":
            scale = d.scale if d.scale is not None else 0.02
        elif d.init == "scaled":
            scale = (d.scale if d.scale is not None else 1.0) / max(1.0, fan_in) ** 0.5
        else:
            scale = d.scale if d.scale is not None else 0.02
        return (jax.random.normal(k, d.shape, jnp.float32) * scale).astype(dtype)

    return _map_schema(make, schema)


def param_specs(schema, rules: ShardingRules, mesh: Optional[Mesh] = None):
    """Pytree of PartitionSpecs mirroring the schema."""
    return _map_schema(lambda _, d: logical_to_spec(d.axes, rules, mesh), schema)


def param_shardings(schema, rules: ShardingRules, mesh: Mesh):
    return _map_schema(
        lambda _, d: NamedSharding(mesh, logical_to_spec(d.axes, rules, mesh)),
        schema)


def abstract_params(schema, param_dtype=jnp.float32):
    """ShapeDtypeStructs for the schema (dry-run: no allocation)."""
    return _map_schema(
        lambda _, d: jax.ShapeDtypeStruct(d.shape, d.dtype or param_dtype),
        schema)


def tree_size_bytes(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for x in leaves:
        total += x.size * x.dtype.itemsize
    return int(total)


def constrain(x: jax.Array, axes: Sequence[Optional[str]],
              rules: Optional[ShardingRules]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op when rules are
    None (smoke tests) or outside a ``jax.set_mesh`` scope.

    NOTE: the mesh must be installed with ``jax.set_mesh(mesh)`` — the
    bare ``with mesh:`` context does
    NOT populate the abstract mesh and silently disables every
    activation constraint (this cost 10x memory in the first dry-run)."""
    if rules is None:
        return x
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = logical_to_spec(axes, rules, mesh if mesh.axis_names else None)
    return jax.lax.with_sharding_constraint(x, spec)
