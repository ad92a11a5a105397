"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — jax locks the device count on
first backend init, and only dryrun.py is allowed to set the
512-placeholder-device XLA flag before that happens.
"""
from __future__ import annotations


import jax

SINGLE_POD = (16, 16)                  # 256 chips (v5e pod)
MULTI_POD = (2, 16, 16)                # 2 pods = 512 chips


def _mk(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devs)} exist; "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count=512 before any jax import")
    return _mk(shape, axes, devices=devs[:n])


def make_host_mesh():
    """Whatever this host has — smoke tests and the CPU train driver."""
    n = len(jax.devices())
    return _mk((n, 1), ("data", "model"))


def make_causal_mesh(*, multi_pod: bool = False):
    """Flat row-parallel mesh for the DML engine (the paper's workload
    has one giant data axis; folds/trials batch inside the program)."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    return mesh  # rows shard over ("data","model") jointly via the
    # "rows" logical axis (see distributed.sharding.default_rules)
