"""Production mesh builders (TPU v5e pods; CPU placeholder devices in CI).

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax

__all__ = ["make_mesh", "make_production_mesh", "data_axes", "MESH_SHAPES"]

MESH_SHAPES = {
    "single": ((16, 16), ("data", "model")),
    "multi": ((2, 16, 16), ("pod", "data", "model")),
}


def make_mesh(shape, axes, *, devices=None):
    """A mesh whose axes are all ``Auto``.

    The code places arrays with ``with_sharding_constraint`` hints
    (``models.layers.maybe_shard``) and ``shard_map``; both assume
    compiler-propagated (``Auto``) axes. ``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which a constraint becomes an assertion.
    """
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """The client/batch axes: everything except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")
