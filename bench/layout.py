"""Where a cell runs: its mesh, and the benchmark's own layout of its weights.

A cell file may carry ``"mesh": {"data": d, "model": m}``; ``d x m`` must be
the chips ``BENCHMARK.json`` gives the cell. Without one the cell runs on one
chip, and its layout is None: weights are made on the default device.

On a mesh, weights are made straight into a layout the benchmark owns:
each leaf split over the cell's devices along its largest axis that their
number divides (the first of equal ones), whole on every device where none
does. It does not follow the program's rule table, so no chip ever holds
the whole model, whatever the program does; the serve engine reshards the
tree into its own layout, as it does for a user's weights. The devices are
taken in the order of the engine's mesh, so that reshard keeps each device's
place. The reference is made again into the same layout and runs over the
same devices.
"""
from __future__ import annotations

import dataclasses
import math

__all__ = ["MESH_AXES", "Layout", "mesh_of", "split_axis", "for_cell"]

MESH_AXES = ("data", "model")
AXIS = "w"


def mesh_of(cell: dict) -> dict | None:
    """The cell's ``mesh``, checked against its ``chips``; None without one."""
    mesh, chips = cell.get("mesh"), cell["chips"]
    if mesh is None:
        if chips != 1:
            raise ValueError(f"cell {cell.get('name')!r} asks for {chips} "
                             "chips but has no mesh")
        return None
    if not isinstance(mesh, dict) or not mesh or set(mesh) - set(MESH_AXES) \
            or not all(isinstance(v, int) and v >= 1 for v in mesh.values()):
        raise ValueError(f"cell {cell.get('name')!r}: mesh {mesh!r} must map "
                         f"some of {MESH_AXES} to whole numbers of at least 1")
    n = math.prod(mesh.values())
    if n != chips:
        raise ValueError(f"cell {cell.get('name')!r}: mesh {mesh} spans {n} "
                         f"devices, but the cell has {chips} chips")
    return dict(mesh)


def split_axis(shape, n: int) -> int | None:
    """The axis of ``shape`` a leaf is split along over ``n`` devices: the
    largest that ``n`` divides, the first of equal ones; None if none."""
    even = [i for i, s in enumerate(shape) if s % n == 0]
    return max(even, key=lambda i: (shape[i], -i)) if even else None


@dataclasses.dataclass(frozen=True)
class Layout:
    """Leaves split over the devices of ``mesh``, a one-axis mesh."""
    mesh: object

    def sharding(self, shape):
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = [None] * len(shape)
        axis = split_axis(shape, self.mesh.size)
        if axis is not None:
            spec[axis] = AXIS
        return NamedSharding(self.mesh, P(*spec))

    def tree(self, shapes):
        """Shardings of a tree whose leaves are shape tuples."""
        import jax
        return jax.tree.map(self.sharding, shapes,
                            is_leaf=lambda x: isinstance(x, tuple))


def for_cell(cell: dict) -> Layout | None:
    """The layout of the cell's weights: None on one chip, else over the
    devices of the serve engine's ``("data", "model")`` mesh, in that mesh's
    order (``jax.make_mesh`` orders a TPU's devices unlike ``jax.devices()``,
    and a tree on the same devices in another order is resharded by a
    compiled program with temporaries of its own)."""
    mesh = mesh_of(cell)
    if mesh is None:
        return None
    import jax
    from repro import compat
    shape = tuple(mesh.get(a, 1) for a in MESH_AXES)
    devices = compat.make_mesh(shape, MESH_AXES).devices.reshape(-1)
    return Layout(jax.sharding.Mesh(devices, (AXIS,)))
