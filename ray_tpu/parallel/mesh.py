"""Device meshes: the declarative backbone of every parallelism strategy.

This replaces the reference's entire tensor-plane stack (SURVEY §5.8: torch
process groups, NCCL/Gloo collective groups, Horovod) with the TPU-native
model: parallelism is *declared* as a `jax.sharding.Mesh` with named axes and
compiled by XLA into ICI/DCN collectives — the mesh is declared, not
connected. The framework's job is only to decide the mesh shape from the
slice topology and hand out shardings.

Axis convention (superset of the reference's §2.4 strategy inventory):

| axis       | strategy                 | typical collective (inserted by XLA) |
|------------|--------------------------|--------------------------------------|
| ``data``   | data parallel            | psum of grads (ICI/DCN all-reduce)   |
| ``fsdp``   | sharded data parallel    | all-gather params, reduce-scatter    |
| ``tensor`` | tensor/Megatron parallel | all-reduce of activations            |
| ``seq``    | sequence/context parallel| ppermute (ring attention)            |
| ``expert`` | expert parallel (MoE)    | all-to-all token routing             |
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("data", "fsdp", "tensor", "seq", "expert")


@dataclass(frozen=True)
class MeshSpec:
    """A named parallelism layout. Sizes must multiply to the device count
    (a -1 entry is inferred, like a reshape).

    ``dcn_data > 1`` declares a MULTI-SLICE layout: that many data-parallel
    replicas across pod slices connected by DCN (the standard multislice
    recipe — gradient all-reduce is the only cross-slice collective, so it
    alone rides the slow network while fsdp/tensor/seq/expert stay on
    intra-slice ICI). The DCN factor folds into the ``data`` mesh axis, so
    sharding rules are unchanged: ``batch`` over ("data", "fsdp") is
    automatically slice-count x per-slice-data parallel."""

    data: int = 1
    fsdp: int = -1   # default: soak up remaining devices as sharded-DP
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    dcn_data: int = 1  # data-parallel replicas across slices (over DCN)

    def sizes(self, n_devices: int) -> Tuple[int, ...]:
        """Final per-axis sizes (dcn folded into data)."""
        if n_devices % self.dcn_data:
            raise ValueError(
                f"{n_devices} devices not divisible across "
                f"{self.dcn_data} slices")
        per_slice = self._ici_sizes(n_devices // self.dcn_data)
        return (per_slice[0] * self.dcn_data,) + per_slice[1:]

    def _ici_sizes(self, n_devices: int) -> Tuple[int, ...]:
        sizes = [self.data, self.fsdp, self.tensor, self.seq, self.expert]
        if sizes.count(-1) > 1:
            raise ValueError("at most one mesh axis may be -1")
        known = math.prod(s for s in sizes if s != -1)
        if -1 in sizes:
            if n_devices % known != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by {known}")
            sizes[sizes.index(-1)] = n_devices // known
        if math.prod(sizes) != n_devices:
            raise ValueError(
                f"mesh {dict(zip(AXES, sizes))} needs {math.prod(sizes)} "
                f"devices, have {n_devices}")
        return tuple(sizes)

    def build(self, devices: Optional[Sequence] = None) -> Mesh:
        """Build the mesh over ``devices`` (default: all addressable).

        Device order: ``jax.experimental.mesh_utils`` places neighbors on ICI
        where possible; multi-slice layouts use
        ``create_hybrid_device_mesh`` so the dcn factor maps to the
        slice boundary (slowest varying). We fall back to a plain reshape on
        CPU/virtual devices (tests use an 8-device virtual CPU mesh, where
        the fallback emulates the slice split)."""
        if devices is None:
            devices = jax.devices()
        devices = np.asarray(devices)
        sizes = self.sizes(devices.size)
        if self.dcn_data > 1:
            ici = self._ici_sizes(devices.size // self.dcn_data)
            dcn = (self.dcn_data, 1, 1, 1, 1)
            on_tpu = _on_tpu(devices)
            try:
                from jax.experimental import mesh_utils

                dev_array = mesh_utils.create_hybrid_device_mesh(
                    ici, dcn, devices=list(devices.flat))
            except Exception:
                if on_tpu:
                    # On real hardware a hybrid-mesh failure means the spec
                    # does not match the slice topology; a silent reshape
                    # would put fsdp/tensor collectives on DCN.
                    raise
                # Virtual/CPU devices carry no slice topology: emulate the
                # slice split with dcn as the slowest-varying factor.
                dev_array = devices.reshape((self.dcn_data,) + ici).reshape(
                    sizes)
            dev_array = dev_array.reshape(sizes)
            return Mesh(dev_array, AXES)
        return Mesh(_ici_device_array(sizes, devices), AXES)


def _on_tpu(devices: np.ndarray) -> bool:
    return any(getattr(d, "platform", "") == "tpu" for d in devices.flat)


def _ici_device_array(shape: Tuple[int, ...], devices: np.ndarray
                      ) -> np.ndarray:
    """Devices laid out so mesh neighbors are ICI neighbors. On TPU a
    ``create_device_mesh`` failure means the shape does not fit the
    slice topology and is re-raised — a silent reshape would build a
    mesh that ignores ICI order. Virtual/CPU devices carry no topology:
    plain reshape."""
    from jax.experimental import mesh_utils

    try:
        return mesh_utils.create_device_mesh(
            shape, devices=list(devices.flat))
    except Exception:
        if _on_tpu(devices):
            raise
        return devices.reshape(shape)


def single_device_mesh() -> Mesh:
    return MeshSpec(data=1, fsdp=1).build(jax.devices()[:1])


# Serving meshes use their own 2-axis naming (SNIPPETS [1]: ``batch`` x
# ``model``): a decode replica has no optimizer state, so the train-side
# data/fsdp/tensor split collapses to "which slots" x "which shard of the
# weights". Kept separate from AXES so train and serve rule tables can't
# cross-contaminate.
DECODE_AXES = ("batch", "model")


def decode_mesh(shape: Tuple[int, int],
                devices: Optional[Sequence] = None) -> Mesh:
    """Named 2-D serving mesh: ``shape = (batch, model)`` over the first
    ``batch * model`` addressable devices (or the explicit ``devices`` a
    sub-slice reservation mapped). ICI ordering comes from
    ``mesh_utils.create_device_mesh`` on real slices; virtual/CPU devices
    fall back to a plain reshape, like :meth:`MeshSpec.build`."""
    b, m = int(shape[0]), int(shape[1])
    if b < 1 or m < 1:
        raise ValueError(f"mesh shape must be positive, got {shape}")
    if devices is None:
        devices = jax.devices()[:b * m]
    devices = np.asarray(devices)
    if devices.size != b * m:
        raise ValueError(
            f"decode mesh {b}x{m} needs {b * m} devices, have "
            f"{devices.size}")
    return Mesh(_ici_device_array((b, m), devices), DECODE_AXES)


# Topology presets keyed by (pod type prefix, device count) intent. These are
# starting points, not laws: the scaling-book recipe is pick mesh -> profile
# -> iterate.
def preset_for(n_devices: int, model_params: int = 0) -> MeshSpec:
    """Heuristic preset: small models pure (fsdp), big models tensor-shard
    within a host (<=8 chips share fastest ICI) and fsdp across."""
    if model_params >= 30_000_000_000 and n_devices >= 8:
        return MeshSpec(tensor=8, fsdp=-1)
    if model_params >= 6_000_000_000 and n_devices >= 4:
        return MeshSpec(tensor=4, fsdp=-1)
    return MeshSpec(fsdp=-1)
