"""Device-mesh construction and canonical shardings.

The reference scales by launching more worker processes against a ZMQ socket
pair (SURVEY.md §2c: pull-based dynamic data parallelism, its only strategy).
Here parallelism is a property of a named `jax.sharding.Mesh`:

- ``data``  — batch-axis DP: B frames split across devices (the analog of
  N workers each pulling a frame, but synchronous, so ordering is free);
- ``space`` — spatial sharding: the H axis of one frame split across
  devices, with XLA GSPMD inserting halo exchanges for stencil/conv ops —
  the framework's long-context analog (SURVEY.md §5.7: "sequence
  parallelism" of a 1080p frame);
- ``model`` — tensor parallelism over filter-internal channels (the style
  net's conv features), unused by pointwise/stencil filters.

All collectives ride ICI when the mesh axes are laid out within a slice;
`make_mesh` defaults to putting ``data`` outermost so DCN-adjacent axes (in
multi-host meshes) carry the lowest-bandwidth traffic — batch scatter/gather
— while halo exchange stays slice-local, per the scaling-book recipe.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "space", "model")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    data: int = 1
    space: int = 1
    model: int = 1

    @property
    def n_devices(self) -> int:
        return self.data * self.space * self.model


def auto_mesh_config(n_devices: int, prefer: str = "data") -> MeshConfig:
    """Factor ``n_devices`` into mesh axes.

    Default policy is all-``data`` (batch DP): for the pointwise/stencil
    filter families, per-frame work fits one chip comfortably and batch DP
    has zero collective traffic — the fastest layout, mirroring the
    reference's choice of pure inter-frame parallelism. ``prefer="space"``
    splits a factor of 2 onto the spatial axis (large-frame configs),
    ``prefer="model"`` onto TP (style-transfer config).
    """
    if prefer == "data" or n_devices == 1:
        return MeshConfig(data=n_devices)
    half = 2 if n_devices % 2 == 0 else 1
    rest = n_devices // half
    if prefer == "space":
        return MeshConfig(data=rest, space=half)
    if prefer == "model":
        return MeshConfig(data=rest, model=half)
    raise ValueError(f"unknown preference {prefer!r}")


def make_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh with axes ('data', 'space', 'model')."""
    devices = list(devices if devices is not None else jax.devices())
    if config is None:
        config = auto_mesh_config(len(devices))
    if config.n_devices > len(devices):
        raise ValueError(
            f"mesh {config} needs {config.n_devices} devices, have {len(devices)}"
        )
    devices = devices[: config.n_devices]
    arr = np.array(devices).reshape(config.data, config.space, config.model)
    return Mesh(arr, AXES)


def batch_pspec(mesh: Mesh, batch_shape: Optional[Sequence[int]] = None) -> P:
    """PartitionSpec for an NHWC frame batch: B over data, H over space.

    C stays replicated — channel counts (3) are far below tile widths; the
    ``model`` axis only shards filter-internal tensors (style net weights).
    If ``batch_shape`` is given, an axis is only sharded when its dimension
    divides evenly (a 4-frame batch on an 8-way data mesh replicates rather
    than erroring — correct, but every device then computes the whole
    batch; Engine.compile says so on stderr and counts each such batch in
    ``EngineStats.replicated_batches``).
    """
    dims = dict(zip(mesh.axis_names, mesh.devices.shape))
    b_ax = dims.get("data", 1)
    h_ax = dims.get("space", 1)
    b = "data" if b_ax > 1 else None
    h = "space" if h_ax > 1 else None
    if batch_shape is not None:
        if b and batch_shape[0] % b_ax != 0:
            b = None
        if h and batch_shape[1] % h_ax != 0:
            h = None
    return P(b, h, None, None)


def batch_sharding(mesh: Mesh, batch_shape: Optional[Sequence[int]] = None) -> NamedSharding:
    return NamedSharding(mesh, batch_pspec(mesh, batch_shape))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_batch_size(b: int, mesh: Mesh) -> int:
    """Round batch up to a multiple of the data-axis size."""
    d = dict(zip(mesh.axis_names, mesh.devices.shape)).get("data", 1)
    return int(math.ceil(b / d) * d)
