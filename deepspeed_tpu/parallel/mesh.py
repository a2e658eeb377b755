"""Device-mesh construction — the spine of all parallelism.

The reference builds torch process groups per parallel dimension
(``deepspeed/utils/groups.py``: DP/TP/EP/SP/hpZ, plus the pipe topology grid in
``runtime/pipe/topology.py``). The TPU-native equivalent is ONE
``jax.sharding.Mesh`` whose named axes carry every parallel dimension; XLA then
lowers sharding annotations to collectives over ICI/DCN. Axis vocabulary:

  - ``data``    — data parallel / ZeRO sharding axis (reference DP + ZeRO groups)
  - ``model``   — tensor parallel (reference TP/mpu groups)
  - ``pipe``    — pipeline stages (reference PipelineParallelGrid)
  - ``seq``     — Ulysses/ring sequence parallel (reference sequence groups)
  - ``expert``  — expert parallel (reference EP groups); folded into ``data``
                  when experts ride the data axis, as the reference's
                  expert-data groups do (groups.py:113-294)

Axis sizes with value ``-1`` absorb the remaining devices (like a reshape).
"""

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, PartitionSpec, NamedSharding

def shard_map_compat(fn, mesh, in_specs, out_specs, check=False, axis_names=None):
    """``jax.shard_map`` with this package's defaults: replication checking
    off, and ``axis_names`` (the MANUAL axes of a partial-manual map: 1F1B
    pipeline, ZeRO++ hpZ) passed only when given."""
    kw = {"axis_names": frozenset(axis_names)} if axis_names is not None else {}
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check, **kw)


DATA_AXIS = "data"
DATA_REPL_AXIS = "data_repl"
MODEL_AXIS = "model"
PIPE_AXIS = "pipe"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"

# The batch dimension spans BOTH data axes. ``data_repl`` is 1 except under
# MiCS (reference ``runtime/zero/mics.py``): with ``mics_shard_size=s`` the
# data dimension splits into (dp/s) x s, ZeRO states shard over the inner
# ``data`` axis only (replicated across ``data_repl``), and XLA's gradient
# psum over both axes lowers to the reference's hierarchical allgather/
# reduce — intra-shard-group traffic on nearest ICI neighbors.
BATCH_AXES = (DATA_REPL_AXIS, DATA_AXIS)

# Canonical axis order: pipe-major so pipeline stages land on contiguous
# device blocks (ICI neighbors), then data, then seq, then model innermost so
# TP rides the fastest ICI links — mirroring the reference's default
# "pipe-data-model" topology order (pipe/topology.py:244) with seq added.
# data_repl sits outside data so a MiCS shard group is an ICI-contiguous block.
AXIS_ORDER = (PIPE_AXIS, DATA_REPL_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS)


@dataclass
class MeshConfig:
    """Axis sizes for the global device mesh (TPU section of the JSON config)."""

    data: int = -1
    data_repl: int = 1  # MiCS replica groups: data_repl = dp / mics_shard_size
    model: int = 1
    pipe: int = 1
    seq: int = 1
    expert: int = 1  # expert <= data * seq; experts shard over (data, seq) axes
    axis_order: Sequence[str] = field(default_factory=lambda: list(AXIS_ORDER))

    def resolve(self, n_devices: int) -> dict:
        sizes = {PIPE_AXIS: self.pipe, DATA_REPL_AXIS: self.data_repl, DATA_AXIS: self.data,
                 SEQ_AXIS: self.seq, MODEL_AXIS: self.model}
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {unknown}")
        known = int(np.prod([v for v in sizes.values() if v != -1]))
        if unknown:
            if n_devices % known != 0:
                raise ValueError(f"device count {n_devices} not divisible by fixed axes product {known}")
            sizes[unknown[0]] = n_devices // known
        total = int(np.prod(list(sizes.values())))
        if total != n_devices:
            raise ValueError(f"mesh axes {sizes} product {total} != device count {n_devices}")
        dp_sp = sizes[DATA_AXIS] * sizes[SEQ_AXIS]
        if self.expert not in (1, ) and dp_sp % self.expert != 0:
            raise ValueError(f"expert parallel size {self.expert} must divide data*seq ({dp_sp})")
        return sizes


def _hybrid_split(shape, axis_order, n_slices):
    """Multi-slice (DCN-connected) pods: decide which mesh axis rides DCN.

    Returns (per_slice_shape, dcn_shape) for
    ``mesh_utils.create_hybrid_device_mesh``. DCN is ~10-100x slower than
    ICI, so the slice boundary must carry the LOWEST-traffic axis: pipe
    (one boundary ppermute per tick) if it spans slices, else data_repl
    (MiCS replica groups — the reference's design point: shard groups inside
    a node/slice, replica reduce across), else plain data (gradient
    reduce once per step, amortized by accumulation). model/seq (per-layer
    collectives) never cross DCN. Raises if no eligible axis divides the
    slice count — a config that would silently put TP on DCN should not
    build.
    """
    for candidate in (PIPE_AXIS, DATA_REPL_AXIS, DATA_AXIS):
        if candidate not in axis_order:  # custom axis orders may omit axes
            continue
        i = list(axis_order).index(candidate)
        if shape[i] % n_slices == 0 and shape[i] >= n_slices:
            per_slice = list(shape)
            per_slice[i] = shape[i] // n_slices
            dcn = [1] * len(shape)
            dcn[i] = n_slices
            return per_slice, dcn
    raise ValueError(
        f"no DCN-eligible axis (pipe/data_repl/data) divisible by the {n_slices} slices in "
        f"mesh {dict(zip(axis_order, shape))}; model/seq must not cross the DCN boundary")


def build_mesh(config: Optional[MeshConfig] = None, devices=None) -> Mesh:
    """Build the global mesh.

    Single slice: device order follows ``jax.devices()`` which on TPU
    enumerates in ICI-topology order; the axis order above therefore keeps
    ``model`` (highest-traffic collectives) on nearest neighbors.

    Multi-slice (DCN): ``mesh_utils.create_hybrid_device_mesh`` with the
    lowest-traffic axis (pipe > data_repl > data) spanning the slice
    boundary — model/seq collectives stay on ICI (``_hybrid_split``).
    """
    config = config or MeshConfig()
    devices = devices if devices is not None else jax.devices()
    sizes = config.resolve(len(devices))
    shape = [sizes[a] for a in config.axis_order]
    try:
        n_slices = len({getattr(d, "slice_index", 0) for d in devices})
    except Exception:
        n_slices = 1
    if n_slices > 1:
        from jax.experimental import mesh_utils

        per_slice, dcn = _hybrid_split(shape, config.axis_order, n_slices)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn, devices=devices, process_is_granule=False)
        from ..utils.logging import log_dist

        log_dist(f"hybrid mesh over {n_slices} DCN slices: per-slice {per_slice} dcn {dcn}",
                 ranks=[0])
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names=tuple(config.axis_order))


def single_device_mesh(device=None) -> Mesh:
    device = device or jax.devices()[0]
    return Mesh(np.asarray([device]).reshape((1, ) * len(AXIS_ORDER)), axis_names=AXIS_ORDER)


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))
