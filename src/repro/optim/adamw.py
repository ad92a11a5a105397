"""AdamW with decoupled weight decay, global-norm clipping and
configurable moment dtype — the optimizer of the MLP nuisance
(``core.nuisance.make_mlp``)."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import TrainConfig


@dataclasses.dataclass
class AdamWState:
    step: jax.Array     # () int32
    m: Any              # pytree like params
    v: Any              # pytree like params

    def tree_flatten(self):
        return (self.step, self.m, self.v), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


jax.tree_util.register_pytree_node(
    AdamWState, AdamWState.tree_flatten, AdamWState.tree_unflatten)


def adamw_init(params, moment_dtype=jnp.float32) -> AdamWState:
    zeros = lambda p: jnp.zeros(p.shape, moment_dtype)
    return AdamWState(step=jnp.zeros((), jnp.int32),
                      m=jax.tree_util.tree_map(zeros, params),
                      v=jax.tree_util.tree_map(zeros, params))


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


def clip_by_global_norm(grads, max_norm: float) -> Tuple[Any, jax.Array]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree_util.tree_map(
        lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), norm


def adamw_update(grads, state: AdamWState, params, lr: jax.Array,
                 cfg: TrainConfig, moment_dtype=jnp.float32
                 ) -> Tuple[Any, AdamWState, Dict[str, jax.Array]]:
    """One decoupled-weight-decay Adam step.  Math in fp32 regardless of
    param/moment dtypes; returns (new_params, new_state, metrics)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip)
    step = state.step + 1
    b1, b2 = cfg.b1, cfg.b2
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m32 = b1 * m.astype(jnp.float32) + (1 - b1) * g32
        v32 = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
        mhat = m32 / c1
        vhat = v32 / c2
        p32 = p.astype(jnp.float32)
        delta = mhat / (jnp.sqrt(vhat) + 1e-8) + cfg.weight_decay * p32
        return ((p32 - lr * delta).astype(p.dtype),
                m32.astype(moment_dtype), v32.astype(moment_dtype))

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, AdamWState(step=step, m=new_m, v=new_v), metrics
