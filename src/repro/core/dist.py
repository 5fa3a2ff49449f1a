"""Distribution context shared by models / training / launch.

Axis convention (TPU-pod adaptation of the paper's wafer coordinates):

* ``pod``   — inter-pod axis (multi-pod data parallelism / pipeline)
* ``data``  — intra-pod data parallelism (batch dim; ZeRO-1 shards)
* ``model`` — the TATP ring axis (sequence/feature streaming), also used for
  expert parallelism in MoE layers and context-parallel KV in serving.

All model code is written in the manual-SPMD style: it executes *inside*
``jax.shard_map`` over the full mesh, with per-shard arrays and explicit
collectives.  This makes every byte of communication visible, which is the
point of the paper (TCME schedules collectives; TATP replaces all-reduce with
one-hop streams).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


BATCH_AXES = ("pod", "data")  # axes that shard the batch dimension
MODEL_AXIS = "model"  # the TATP ring axis


def make_mesh(shape: Sequence[int], names: Sequence[str],
              devices=None) -> Mesh:
    return jax.make_mesh(tuple(shape), tuple(names),
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


@dataclass(frozen=True)
class Dist:
    """Static distribution descriptor, safe to close over in jitted code."""

    mesh: Mesh
    batch_axes: tuple[str, ...] = BATCH_AXES
    model_axis: str = MODEL_AXIS

    @cached_property
    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    @property
    def present_batch_axes(self) -> tuple[str, ...]:
        return tuple(a for a in self.batch_axes if a in self.axis_sizes)

    @property
    def model_degree(self) -> int:
        return self.axis_sizes.get(self.model_axis, 1)

    @property
    def batch_degree(self) -> int:
        n = 1
        for a in self.present_batch_axes:
            n *= self.axis_sizes[a]
        return n

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    # ------------------------------------------------------------------
    # sharding helpers (global-view; used at jit boundaries)
    # ------------------------------------------------------------------
    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    def batch_spec(self, batch_size: int, ndim: int = 2) -> P:
        """Shard dim 0 over the batch axes when divisible, else replicate."""
        axes = self.present_batch_axes
        deg = self.batch_degree
        first = axes if (deg > 1 and batch_size % deg == 0) else None
        return P(first, *([None] * (ndim - 1)))

    def seq_spec(self, batch_size: int, ndim: int = 2) -> P:
        """(batch over data axes when divisible) × (seq over model axis)."""
        axes = self.present_batch_axes
        deg = self.batch_degree
        first = axes if (deg > 1 and batch_size % deg == 0) else None
        return P(first, self.model_axis, *([None] * (ndim - 2)))


def local_slice(dist: Dist, x_shape_dim: int, axis: str) -> int:
    return x_shape_dim // dist.axis_sizes.get(axis, 1)


# ------------------------------------------------------------------
# in-shard_map helpers
# ------------------------------------------------------------------


def axis_index(axis: str):
    return jax.lax.axis_index(axis)


def psum_batch(x, dist: Dist):
    for a in dist.present_batch_axes:
        x = jax.lax.psum(x, a)
    return x


def pmean_batch(x, dist: Dist):
    for a in dist.present_batch_axes:
        x = jax.lax.pmean(x, a)
    return x
