"""Production mesh definition.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count is locked at first jax init; the dry-run sets
XLA_FLAGS before importing anything else).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the models place arrays with
    sharding hints and let XLA propagate the rest, which explicit axes
    (``jax.make_mesh``'s default) would refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod ('data','model'); 2 pods = 512 chips with a
    leading pure-DP 'pod' axis that crosses the slow inter-pod links exactly
    once per step (gradient all-reduce)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has -- smoke tests and examples."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))
