"""Data substrate: DGP determinism + ground truth, prefetching feed
ordering."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.causal_dgp import (make_causal_data,
                                   make_sharded_causal_data)
from repro.data.pipeline import ShardedFeed


def test_dgp_deterministic(key):
    d1 = make_causal_data(key, 500, 8)
    d2 = make_causal_data(key, 500, 8)
    np.testing.assert_array_equal(np.asarray(d1.X), np.asarray(d2.X))
    np.testing.assert_array_equal(np.asarray(d1.y), np.asarray(d2.y))


def test_dgp_ground_truth_consistent(key):
    d = make_causal_data(key, 50_000, 10, effect=2.0, heterogeneous=True)
    assert d.true_ate == pytest.approx(float(d.true_cate.mean()))
    # overlap: propensities bounded away from {0,1}
    assert 0.001 < float(d.propensity.min())
    assert float(d.propensity.max()) < 0.999
    # naive difference-in-means is confounded (differs from truth)
    t = d.t
    naive = float((d.y * t).sum() / t.sum()
                  - (d.y * (1 - t)).sum() / (1 - t).sum())
    assert abs(naive - d.true_ate) > 0.05


def test_sharded_dgp_unions(key):
    shards = [make_sharded_causal_data(key, 100, 4, 4, s) for s in range(4)]
    assert all(s.X.shape == (25, 4) for s in shards)
    # shards differ (independent folds of the key)
    assert not np.allclose(np.asarray(shards[0].X), np.asarray(shards[1].X))


def test_sharded_feed_order_and_close(key):
    feed = ShardedFeed(lambda s: {"x": jnp.full((2,), s)}, depth=2)
    got = [int(next(feed)["x"][0]) for _ in range(5)]
    assert got == [0, 1, 2, 3, 4]
    assert feed.step == 5
    feed.close()


def test_sharded_feed_propagates_errors():
    def boom(s):
        if s == 1:
            raise ValueError("generator failed")
        return {"x": jnp.zeros(())}

    feed = ShardedFeed(boom, depth=1)
    next(feed)
    with pytest.raises(ValueError, match="generator failed"):
        next(feed)
        next(feed)
    feed.close()
