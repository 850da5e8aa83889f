"""Device mesh construction over ICI/DCN.

This is the substrate that replaces the reference's MPI/NCCL world
(mlrun/runtimes/mpijob/abstract.py:89-96 NCCL env defaults; Horovod init in
frameworks/pytorch/mlrun_interface.py:561-566): instead of ranks + explicit
allreduce, we build a ``jax.sharding.Mesh`` whose axes map onto the TPU
interconnect — ICI within a pod-slice, DCN across slices — and let XLA emit
the collectives from sharding annotations.

Mesh axis convention (configurable, cf. config.tpu.mesh):
  data   — pure data parallelism (usually across slices / DCN)
  fsdp   — fully-sharded data parallel (params sharded, ICI)
  tensor — tensor/model parallelism (ICI, innermost = fastest axis)
  seq    — optional sequence/context parallelism axis for ring attention
"""

from __future__ import annotations

import math
import os
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

DEFAULT_AXES = ("data", "fsdp", "tensor")


@dataclass(frozen=True)
class MeshConfig:
    """Declarative logical mesh description."""

    shape: dict  # axis name -> size; -1 for "fill with remaining devices"
    num_slices: int = 1

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.shape.keys())

    def resolve(self, n_devices: int) -> dict:
        """Resolve -1 axes against the available device count."""
        shape = dict(self.shape)
        known = 1
        fill_axis = None
        for axis, size in shape.items():
            if size == -1:
                if fill_axis is not None:
                    raise ValueError("only one mesh axis may be -1")
                fill_axis = axis
            else:
                known *= size
        if fill_axis is not None:
            if n_devices % known:
                raise ValueError(
                    f"cannot fill axis '{fill_axis}': {n_devices} devices "
                    f"not divisible by {known}")
            shape[fill_axis] = n_devices // known
            known *= shape[fill_axis]
        if known != n_devices:
            raise ValueError(
                f"mesh shape {shape} needs {known} devices, have {n_devices}")
        return shape


def make_mesh(shape: dict | None = None, devices=None,
              num_slices: int | None = None,
              axis_names: Sequence[str] | None = None) -> Mesh:
    """Build a Mesh.

    - single slice: ``jax.make_mesh`` (toroidal-aware device order)
    - multi slice: hybrid ICI×DCN mesh via
      ``jax.experimental.mesh_utils.create_hybrid_device_mesh`` — the FIRST
      axis (conventionally ``data``) spans slices over DCN, the rest ride ICI.
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    if shape is None:
        axis_names = tuple(axis_names or DEFAULT_AXES)
        # default: everything on fsdp
        shape = {name: 1 for name in axis_names}
        shape[axis_names[1] if len(axis_names) > 1 else axis_names[0]] = n
    config = MeshConfig(shape)
    explicit = [s for s in shape.values() if s != -1]
    product = int(np.prod(explicit)) if explicit else 0
    if -1 not in shape.values() and 0 < product < n:
        # smaller explicit mesh than available devices → use a prefix
        devices = list(devices)[:product]
        n = product
    resolved = config.resolve(n)
    names = tuple(resolved.keys())
    sizes = tuple(resolved.values())

    num_slices = num_slices or _detect_num_slices(devices)
    # Auto axis types: we annotate params/data in/out shardings and let
    # GSPMD propagate + insert collectives (jax 0.9 defaults to Explicit,
    # which demands per-op sharding types instead).
    mesh_kwargs = {"axis_types": (AxisType.Auto,) * len(names)}
    if num_slices > 1:
        from jax.experimental.mesh_utils import create_hybrid_device_mesh

        if sizes[0] % num_slices:
            raise ValueError(
                f"first (DCN) axis size {sizes[0]} must be divisible by "
                f"num_slices {num_slices}")
        dcn = (num_slices,) + (1,) * (len(sizes) - 1)
        ici = (sizes[0] // num_slices,) + sizes[1:]
        try:
            device_array = create_hybrid_device_mesh(
                ici, dcn, devices=devices, allow_split_physical_axes=True)
        except (ValueError, AttributeError, KeyError):
            if any(getattr(d, "slice_index", None) is not None
                   for d in devices):
                # real multi-slice hardware: this is a genuine topology/
                # declaration error — degrading to an arbitrary device
                # order would silently misalign the DCN axis
                raise
            # CPU/virtual devices carry no slice_index/DCN topology
            # (the MLT_NUM_SLICES override and elastic tests run here):
            # contiguous device blocks stand in for slices — correct
            # semantics, just without the DCN-aware device ordering
            device_array = np.asarray(devices).reshape(sizes)
        return Mesh(device_array, names, **mesh_kwargs)
    return jax.make_mesh(sizes, names, devices=devices, **mesh_kwargs)


def _detect_num_slices(devices) -> int:
    """Slice count of a device set. ``MLT_NUM_SLICES`` overrides (virtual
    multi-slice on CPU — the elastic tests' backbone); otherwise the
    devices' ``slice_index`` attribute, with an explicit CPU/virtual
    fallback: a backend without slice topology reports 1 slice, never
    raises."""
    env = os.environ.get("MLT_NUM_SLICES", "")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass  # a malformed override degrades to detection
    try:
        slice_ids = {getattr(d, "slice_index", 0) or 0 for d in devices}
    except Exception:  # noqa: BLE001 - attribute probing on exotic
        return 1       # backends (virtual/plugin devices) must not raise
    return max(1, len(slice_ids))


def refit_shape(shape: dict, n_devices: int,
                prefer_axis: str | None = None) -> dict:
    """Refit a resolved mesh shape onto a new device count by rescaling
    ONE axis — ``prefer_axis`` first, then declaration order (the first
    axis is conventionally the DCN/data axis that spans slices, so a
    slice loss shrinks it). This is the elastic trainer's mesh-shrink/
    grow rule: survivors of a slice preemption rebuild their mesh with
    ``make_mesh(refit_shape(old_shape, len(survivors)), survivors)``.
    Raises ValueError when no single axis rescales evenly."""
    order = ([prefer_axis] if prefer_axis in shape else []) + list(shape)
    for axis in order:
        trial = dict(shape)
        trial[axis] = -1
        try:
            return MeshConfig(trial).resolve(n_devices)
        except ValueError:
            continue
    raise ValueError(
        f"cannot refit mesh shape {shape} onto {n_devices} devices: no "
        "single axis rescales evenly")


def local_mesh(n: int | None = None, axis_names: Sequence[str] = ("data",)
               ) -> Mesh:
    """A 1-axis mesh over local devices (tests / single host)."""
    devices = jax.devices()
    n = n or len(devices)
    return make_mesh({axis_names[0]: n}, devices=devices[:n])


def mesh_shape_for_topology(topology: str, chips_per_host: int = 4,
                            num_slices: int = 1,
                            model_parallel: int = 1) -> dict:
    """Suggest a (data, fsdp, tensor) shape for a TPU topology string."""
    dims = [int(d) for d in topology.lower().split("x")]
    chips = int(np.prod(dims))
    total = chips * num_slices
    if total % model_parallel:
        raise ValueError(
            f"{total} chips not divisible by tensor={model_parallel}")
    return {"data": num_slices, "fsdp": total // num_slices // model_parallel,
            "tensor": model_parallel}


def initialize_distributed(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None):
    """Multi-host init (replaces hvd.init/mpirun; on GKE JobSet the TPU env
    supplies everything and bare ``jax.distributed.initialize()`` works)."""
    import os

    # NOTE: decide from env only — jax.process_count() would initialize the
    # XLA backend and make jax.distributed.initialize() fail afterwards
    multi_host = bool(
        coordinator_address
        or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ
        or (os.environ.get("TPU_WORKER_HOSTNAMES", "").count(",") >= 1))
    if not multi_host:
        return
    from jax._src import distributed as _dist

    if getattr(_dist.global_state, "client", None) is not None:
        return  # already initialized
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def mesh_info(mesh: Mesh) -> dict:
    return {
        "axis_names": list(mesh.axis_names),
        "shape": dict(mesh.shape),
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "devices": str(mesh.devices.ravel()[0].platform),
    }
