"""Optimizer substrate of the MLP nuisance: AdamW math and clipping."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TrainConfig
from repro.optim.adamw import adamw_init, adamw_update, clip_by_global_norm


def test_adamw_minimizes_quadratic(key):
    w = {"x": jax.random.normal(key, (16,))}
    cfg = TrainConfig(learning_rate=0.1, weight_decay=0.0, grad_clip=1e9)
    opt = adamw_init(w)
    loss = lambda p: 0.5 * jnp.sum(p["x"] ** 2)
    for _ in range(200):
        g = jax.grad(loss)(w)
        w, opt, _ = adamw_update(g, opt, w, jnp.float32(0.1), cfg)
    assert float(loss(w)) < 1e-4


def test_weight_decay_is_decoupled(key):
    """With zero gradients, params shrink by exactly lr*wd*p."""
    w = {"x": jnp.ones((4,))}
    cfg = TrainConfig(learning_rate=0.0, weight_decay=0.1, grad_clip=1e9)
    opt = adamw_init(w)
    g = {"x": jnp.zeros((4,))}
    w2, _, _ = adamw_update(g, opt, w, jnp.float32(0.5), cfg)
    np.testing.assert_allclose(np.asarray(w2["x"]),
                               1.0 - 0.5 * 0.1 * 1.0, rtol=1e-6)


def test_clip_by_global_norm(key):
    g = {"a": jnp.full((10,), 3.0), "b": jnp.full((10,), 4.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(10 * 9 + 10 * 16))
    from repro.optim.adamw import global_norm
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)

