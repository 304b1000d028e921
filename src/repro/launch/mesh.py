"""Mesh construction — the one place a ``jax.sharding.Mesh`` is built.

Defined as functions (not module constants) so importing never touches
jax device state. The dry-run sets XLA_FLAGS for 512 host devices before
any jax import; tests/benches see the real single device.

Every axis is ``AxisType.Auto``. In jax 0.9 ``jax.make_mesh`` defaults to
*Explicit* axes, under which sharding is part of an array's type and
ops such as the embedding gather refuse to guess an output sharding; the
model code here is written for GSPMD propagation guided by
``with_sharding_constraint`` (sharding/rules.py), which is what Auto
axes give.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

from repro.sharding.rules import Rules


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], *,
              devices: Optional[Sequence] = None):
    """A mesh of ``shape`` over named ``axes`` with Auto axis types.

    ``devices`` defaults to ``jax.devices()``; pass a described
    topology's devices (``jax.experimental.topologies``) to lower for a
    chip that is not attached."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_rules(mesh, table: Optional[dict] = None) -> Rules:
    return Rules(mesh, table)


def smoke_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU tests (requires >= data*model fake devices)."""
    n = len(jax.devices())
    data = min(data, max(n // model, 1))
    if data * model > n:
        model = n // data
    return make_mesh((data, model), ("data", "model"))
