"""Production meshes (TPU v5e): 16x16 single pod, 2x16x16 two pods.

``make_production_mesh`` is a function (not a module constant) so importing
this module never touches jax device state; the dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
use.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: sharding propagates from the
    ``NamedSharding``s and constraints the program places, as the rules
    in ``repro.sharding`` assume (JAX's default is ``Explicit``, which
    types every array by its sharding and rejects those constraints)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(*, data: int = 2, model: int = 2):
    """Small mesh over however many (possibly fake) local devices exist —
    used by sharding unit tests."""
    n = len(jax.devices())
    assert n >= data * model, (n, data, model)
    return _mesh((data, model), ("data", "model"))


def make_serving_mesh(*, data: int = 1, model: int = 1):
    """The serving runtime's ``("data", "model")`` mesh, or ``None`` for
    the 1x1 degenerate case — the scheduler skips every device_put and
    stays bit-identical to the pre-mesh runtime. Single-process today
    (real chips on TPU, fake CPU devices under
    ``XLA_FLAGS=--xla_force_host_platform_device_count`` in CI); the
    axis names match ``make_production_mesh`` so multi-process is a
    mesh-construction swap, not a rules rewrite."""
    if data <= 1 and model <= 1:
        return None
    n = len(jax.devices())
    assert n >= data * model, \
        f"serving mesh {data}x{model} needs {data * model} devices, " \
        f"have {n}"
    return _mesh((data, model), ("data", "model"))


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
