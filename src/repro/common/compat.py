"""The repo's one entry point for JAX mesh and tree APIs.

Every caller reaches ``shard_map``, mesh construction and path-aware tree
maps through this module, so a future API move is a one-file change.
The repo runs one JAX generation (0.9); there are no version fallbacks.

- ``shard_map`` keeps the repo's ``auto=`` spelling for partial-manual
  maps: the axes NOT listed in ``auto`` are manual, which is what
  ``jax.shard_map``'s ``axis_names=`` takes. ``check_rep`` is accepted as
  an alias of ``check_vma``; both default to disabled.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["tree_flatten_with_path", "make_mesh", "shard_map"]

tree_flatten_with_path = jax.tree.flatten_with_path


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` over ``devices`` (default: all local devices).

    Every axis is ``Auto``: layouts come from the jit in/out shardings
    and the partitioner, as the repo's step bundles expect. Drive a
    compiled step inside the mesh's own ``with mesh:`` block, not
    ``jax.set_mesh``: the latter commits every array created inside it
    to the mesh, replicated, and a compiled step refuses committed
    arguments whose sharding differs from its declared one."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(f, mesh, *, in_specs, out_specs, auto=frozenset(),
              check_rep=None, check_vma=None):
    """``jax.shard_map`` with every mesh axis outside ``auto`` manual.

    An empty ``auto`` is a fully-manual map. A non-empty one leaves those
    axes to the partitioner (partial-auto), mapped onto ``axis_names``.
    """
    check = check_rep if check_rep is not None else bool(check_vma)
    unknown = set(auto) - set(mesh.axis_names)
    if unknown:
        raise ValueError(f"auto axes {sorted(unknown)} are not mesh axes "
                         f"{mesh.axis_names}")
    manual = frozenset(a for a in mesh.axis_names if a not in auto)
    if not manual:
        raise ValueError("shard_map needs at least one manual axis; "
                         f"auto={sorted(auto)} covers the whole mesh")
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=manual,
                         check_vma=check)
