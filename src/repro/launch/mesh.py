"""Mesh construction.

A FUNCTION (never a module-level constant) so importing this module
never touches jax device state: jax locks the device count on first
backend init.
"""
from __future__ import annotations


import jax


def _mk(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
