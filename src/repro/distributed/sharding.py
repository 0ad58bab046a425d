"""Sharding context threaded through model code.

Models never import a concrete mesh; they call ``shard.act(x, *axes)`` with
*logical* axis names and the Sharder resolves them to mesh axes (or becomes a
no-op on a single device, which is what smoke tests use).

Logical axes:
  "batch"  -> all data-parallel mesh axes (("pod", "data") on the multi-pod mesh)
  "model"  -> the tensor-parallel mesh axis
  "seq"    -> sequence dim; maps to "model" when sequence-parallelism is on
  None     -> replicated dim

Internal activation constraints may be uneven (GSPMD pads); parameter
in_shardings must divide evenly — configs pick padded physical dims.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NO_SHARD = None

__all__ = ["Sharder", "NO_SHARD", "batch_partition_axes"]


def batch_partition_axes(mesh: Mesh) -> tuple:
    """Mesh axes a batch/window dimension shards over.

    The data-parallel axes when the mesh names any (``Sharder.for_mesh``'s
    resolution: "pod" / "data" / "replica"), every mesh axis otherwise — a
    1-D ad-hoc mesh of any axis name is fully data-parallel.
    """
    axes = Sharder.for_mesh(mesh).data_axes
    return axes if axes else tuple(mesh.axis_names)


@dataclass
class Sharder:
    mesh: Mesh | None = None
    data_axes: tuple = ("data",)
    model_axis: str = "model"
    seq_parallel: bool = False
    # gradient-compression hook (distributed/collectives.py wraps DP psums)
    grad_compression: str | None = None

    @classmethod
    def for_mesh(cls, mesh: Mesh | None, *, seq_parallel: bool = False,
                 grad_compression: str | None = None) -> "Sharder":
        if mesh is None:
            return cls(None)
        names = mesh.axis_names
        data_axes = tuple(a for a in names if a in ("pod", "data", "replica"))
        model_axis = "model" if "model" in names else None
        return cls(mesh, data_axes, model_axis, seq_parallel, grad_compression)

    # -- logical resolution ---------------------------------------------------
    def _resolve(self, axis: str | None):
        if axis is None:
            return None
        if axis == "batch":
            return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        if axis == "model":
            return self.model_axis
        if axis == "seq":
            return self.model_axis if self.seq_parallel else None
        if axis == "data":
            return self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        if axis == "flat":
            # every mesh axis: the maximal sharding (GNN edge/node arrays)
            axes = tuple(self.data_axes) + ((self.model_axis,) if self.model_axis else ())
            return axes if len(axes) > 1 else (axes[0] if axes else None)
        raise ValueError(f"unknown logical axis {axis!r}")

    def spec(self, *axes) -> P:
        return P(*[self._resolve(a) for a in axes])

    def named(self, *axes) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(*axes))

    # -- activation constraint --------------------------------------------------
    def act(self, x: jax.Array, *axes) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.named(*axes))

    # -- parameter sharding resolution -------------------------------------------
    def params(self, spec_tree, param_tree):
        """Map a pytree of logical-axis tuples to NamedShardings (or None)."""
        if self.mesh is None:
            return jax.tree.map(lambda _: None, param_tree)
        return jax.tree.map(
            lambda axes: NamedSharding(self.mesh, self.spec(*axes)),
            spec_tree,
            is_leaf=lambda x: isinstance(x, tuple),
        )
