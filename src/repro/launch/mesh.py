"""Production mesh factory.

Defined as FUNCTIONS (not module constants) so importing this module
never touches jax device state.  TPU v5e target:
  single pod:  (16, 16)    axes ("data", "model")   = 256 chips
  multi-pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules and
    ``with_sharding_constraint`` calls are hints to the partitioner
    (``make_mesh`` defaults to ``Explicit`` axes, under which those
    constraints become type assertions)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke/serving runs."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_routing_mesh(n_devices: int | None = None):
    """1-D mesh for the mega-catalog sharded ``route_step``: the
    catalog (N) axis of every routing operand shards over its single
    ``"catalog"`` axis (``sharding.rules.CATALOG_AXIS``); queries stay
    replicated.  Defaults to all visible devices — on a CPU CI box set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (or more)
    to exercise the cross-device program."""
    from repro.sharding.rules import CATALOG_AXIS
    nd = jax.device_count() if n_devices is None else int(n_devices)
    assert 1 <= nd <= jax.device_count(), (nd, jax.device_count())
    return _auto_mesh((nd,), (CATALOG_AXIS,))
