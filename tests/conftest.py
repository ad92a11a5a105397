# Tests run on the host's single CPU device — a forced host device
# count must never be set here (jax locks the device count at first
# init; the distributed suite takes its own XLA_FLAGS, see CI).
import jax
import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Release compiled executables between test modules.

    Every XLA executable holds live memory mappings; across the full
    suite the process otherwise accumulates past the kernel's default
    ``vm.max_map_count`` (65530) and a late compile segfaults inside
    XLA.  Cross-module cache hits are rare (each module compiles its
    own shapes), so this costs little and bounds the map count.  The
    bit-identity contracts are all certified within one module, never
    across a cache clear."""
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _reset_default_metrics_registry():
    """Fresh process-global metrics registry per test.

    ``obs.metrics.default_registry()`` is a process-wide singleton
    (trace-time instrumentation can't thread a handle); without a reset
    any two tests touching a same-name counter couple through test
    order.  Counts within one test remain visible — instrumentation
    re-resolves ``default_registry()`` on every increment."""
    from repro.obs.metrics import reset_default_registry

    reset_default_registry()
    yield


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def assert_no_nan(tree, where=""):
    import jax.numpy as jnp
    for leaf in jax.tree_util.tree_leaves(tree):
        assert jnp.isfinite(leaf).all(), f"non-finite values {where}"
