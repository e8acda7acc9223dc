"""Parallelism context threaded through model code.

Keeps the model definitions mesh-agnostic: every distribution decision is a
`constrain` (GSPMD sharding hint) or an explicit shard_map wrap (MoE expert
parallelism), all of which degrade to no-ops when ``mesh is None`` (CPU smoke
tests run the identical code path).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    mesh: Optional[Mesh] = None
    tp_axis: Optional[str] = "model"
    # str, or tuple for cross-pod FSDP (ZeRO over DCN: ("pod", "data")).
    fsdp_axis = "data"
    pod_axis: Optional[str] = "pod"

    def __init__(self, mesh=None, tp_axis="model", fsdp_axis="data", pod_axis="pod"):
        object.__setattr__(self, "mesh", mesh)
        if mesh is not None:
            names = mesh.axis_names
            tp_axis = tp_axis if tp_axis in names else None
            pod_axis = pod_axis if pod_axis in names else None
            if isinstance(fsdp_axis, tuple):
                fs = tuple(a for a in fsdp_axis if a in names)
                fsdp_axis = fs if len(fs) > 1 else (fs[0] if fs else None)
            else:
                fsdp_axis = fsdp_axis if fsdp_axis in names else None
        object.__setattr__(self, "tp_axis", tp_axis)
        object.__setattr__(self, "fsdp_axis", fsdp_axis)
        object.__setattr__(self, "pod_axis", pod_axis)

    @property
    def dp_axes(self) -> Tuple[str, ...]:
        """Axes the batch is sharded over."""
        axes = []
        if self.pod_axis:
            axes.append(self.pod_axis)
        fs = self.fsdp_axis if isinstance(self.fsdp_axis, tuple) else (
            (self.fsdp_axis,) if self.fsdp_axis else ())
        for a in fs:
            if a not in axes:
                axes.append(a)
        return tuple(axes)

    @property
    def batch_spec(self):
        return tuple(self.dp_axes) or None

    def axis_size(self, name) -> int:
        if self.mesh is None or name is None:
            return 1
        if isinstance(name, tuple):
            out = 1
            for a in name:
                out *= self.mesh.shape[a]
            return out
        return self.mesh.shape[name]

    def constrain(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, P(*spec)))

    def divides(self, dim: int, axis) -> bool:
        return axis is not None and dim % self.axis_size(axis) == 0

    def seq_spec(self, seq_len: int) -> Optional[str]:
        """Sequence-parallel axis for activations between layers (Megatron-SP):
        residual-stream tensors are sharded over the TP axis on the sequence
        dim wherever it divides; GSPMD inserts the all-gather at attention
        and the reduce-scatter after. Cuts saved-activation memory by |tp|."""
        if self.tp_axis is not None and seq_len % self.axis_size(self.tp_axis) == 0 and seq_len > 1:
            return self.tp_axis
        return None


NO_PARALLEL = ParallelCtx(mesh=None, tp_axis=None, fsdp_axis=None, pod_axis=None)
