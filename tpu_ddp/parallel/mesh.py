"""Device mesh construction and sharding helpers.

The mesh is N-dimensional from day one (SURVEY.md §5.7): the reference only
exercises data parallelism, but ``MeshSpec`` reserves named axes for tensor,
pipeline, sequence, and expert parallelism so scaling out is a config change,
not a redesign. Collectives ride ICI within a pod slice and DCN across pods —
axis order puts ``data`` outermost (DCN-friendly) and ``model`` innermost
(ICI-friendly), per the standard TPU sharding recipe.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
SEQUENCE_AXIS = "sequence"
PIPELINE_AXIS = "pipeline"
MODEL_AXIS = "model"
EXPERT_AXIS = "expert"

# Outermost-to-innermost: cross-host friendly axes first, ICI-hungry last.
AXIS_ORDER = (DATA_AXIS, PIPELINE_AXIS, EXPERT_AXIS, SEQUENCE_AXIS, MODEL_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Sizes for each mesh axis; -1 on exactly one axis means "all remaining
    devices". Axes of size 1 are kept in the mesh (free to re-use later)."""

    data: int = -1
    pipeline: int = 1
    expert: int = 1
    sequence: int = 1
    model: int = 1

    def resolve(self, n_devices: int) -> dict:
        sizes = {
            DATA_AXIS: self.data,
            PIPELINE_AXIS: self.pipeline,
            EXPERT_AXIS: self.expert,
            SEQUENCE_AXIS: self.sequence,
            MODEL_AXIS: self.model,
        }
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh wants {fixed} devices, have {n_devices}")
        return sizes


def create_mesh(
    spec: MeshSpec | None = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh over the given (default: all) devices.

    Replaces the reference's world-size discovery + per-rank process spawn
    (``main.py:80-84``): here one process addresses every device through a
    single mesh, and "rank" is just a coordinate on the ``data`` axis.
    """
    spec = spec or MeshSpec()
    devices = list(devices) if devices is not None else jax.devices()
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def assert_process_contiguous_data_axis(mesh: Mesh, process_count: int) -> None:
    """Multi-host data loading assumes host h's addressable devices occupy
    the CONTIGUOUS block [h*lws, (h+1)*lws) of the data axis — the loader
    yields exactly those rows and ``make_array_from_process_local_data``
    places them by sharding, so a mesh built with a non-process-contiguous
    device order would silently train on mis-assigned rows. This holds for
    ``jax.devices()`` ordering today; this check turns the assumption into
    a loud error instead of silent data corruption."""
    if process_count <= 1:
        return
    dev = mesh.devices  # (data, pipeline, expert, sequence, model)
    data_size = dev.shape[0]
    if data_size % process_count:
        raise RuntimeError(
            f"data axis ({data_size}) not divisible by process count "
            f"({process_count}); multi-host loading needs equal host blocks"
        )
    per_host = data_size // process_count
    for d in range(data_size):
        expect = d // per_host
        owners = {dd.process_index for dd in dev[d].ravel()}
        if owners != {expect}:
            raise RuntimeError(
                f"mesh data-axis row {d} is owned by processes "
                f"{sorted(owners)}, expected exactly process {expect}: "
                "the device order is not process-contiguous, so host-local "
                "batch rows would land on the wrong devices. Build the "
                "mesh from jax.devices() order (create_mesh default)."
            )


def data_parallel_mesh(n: Optional[int] = None) -> Mesh:
    devices = jax.devices()[:n] if n else None
    return create_mesh(MeshSpec(data=-1), devices)


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Shard the leading (batch) dimension over `axis`; replicate the rest.

    This single annotation replaces the reference's ``DistributedSampler``
    rank math + per-process loaders (``main.py:60-61``) at the device level.
    """
    return NamedSharding(mesh, P(axis))


def stacked_batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Sharding for a K-stacked batch (K, global_batch, ...): the scan axis
    is replicated, the batch axis sharded — the input layout of
    ``make_train_step(steps_per_call=K)``."""
    return NamedSharding(mesh, P(None, axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated — the reference's DDP model replication
    (``main.py:62-63``) without the wrapper or the ctor broadcast."""
    return NamedSharding(mesh, P())
