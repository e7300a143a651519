"""Mesh helpers shared by ``core/``, ``parallel/``, ``launch/`` and ``models/``.

Two conventions that plain JAX calls do not give:

* ``make_mesh`` builds meshes whose axes are ``AxisType.Auto``, so GSPMD
  places whatever the sharding rules leave open (``jax.make_mesh`` defaults
  to ``Explicit`` axes);
* ``get_abstract_mesh`` answers "is a mesh with axes active?" with ``None``
  when none is (JAX returns an empty abstract mesh instead).

Everything else (``jax.set_mesh``, ``jax.shard_map``, ``jax.lax.axis_size``)
is called directly.
"""
from __future__ import annotations

import jax

__all__ = ["make_mesh", "get_abstract_mesh"]


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(tuple(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types,
                         devices=devices)


def get_abstract_mesh():
    """The active mesh, or ``None`` when no mesh with axes is active."""
    m = jax.sharding.get_abstract_mesh()
    if m is None or not m.axis_names:
        return None
    return m
