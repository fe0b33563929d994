"""Production mesh construction and the mesh helpers the launch and
sharding paths (and the tests) share.

Kept as functions (never module-level constants) so importing this
module never touches jax device state — required because the dry-run
must set XLA_FLAGS before any jax initialisation.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_compat_mesh(shape, names):
    """``jax.make_mesh`` with every axis explicitly Auto-sharded.
    Every mesh the launch path or the test suite builds goes through
    here."""
    shape = tuple(int(s) for s in shape)
    names = tuple(names)
    return jax.make_mesh(shape, names,
                         axis_types=(AxisType.Auto,) * len(names))


def set_mesh(mesh):
    """Context manager making ``mesh`` the ambient mesh."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with replication checking off by default (the
    repo's callers all pass explicit out_specs)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def jit_shardings(mesh, tree):
    """Map a tree of ``PartitionSpec`` leaves onto ``NamedSharding``
    for ``jax.jit(in_shardings=...)``, so that the shardings do not
    depend on an ambient mesh."""
    from jax.sharding import NamedSharding, PartitionSpec
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree, is_leaf=lambda x: isinstance(x, PartitionSpec))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; the multi-pod mesh adds a leading
    'pod' axis (2 pods = 512 chips).  'pod' is an outer data-parallel
    axis: scaling to N pods only grows this axis (elastic by
    construction — see DESIGN.md §5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_compat_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(1, n // data))
    return make_compat_mesh((data, model), ("data", "model"))
