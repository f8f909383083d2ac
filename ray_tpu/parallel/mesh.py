"""Device mesh construction — the TPU-native heart of all parallelism.

In the reference, parallelism strategies are scattered across engines
(torch DDP in train/torch/train_loop_utils.py:178, FSDP at :187, vLLM
TP/PP via ray.llm). In a TPU-first design they are all *mesh-axis
shardings of one jitted program* (SURVEY.md §2.3): we define one
canonical set of axis names and build `jax.sharding.Mesh` objects over
ICI (intra-slice) and DCN (cross-slice) from a small declarative spec.

Axis convention (outer → inner, DCN-attached axes first so cross-slice
traffic rides DCN and everything else rides ICI):

    replica   : cross-slice data parallelism (DCN)
    data      : in-slice data parallelism / batch sharding (DP)
    fsdp      : ZeRO-style parameter/optimizer sharding (FSDP)
    stage     : pipeline stages (PP)
    expert    : MoE expert sharding (EP)
    sequence  : sequence/context parallelism (SP/CP, ring attention)
    tensor    : model/tensor parallelism (TP, Megatron-style)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

# Canonical axis order. Outer axes get the "slower" interconnect.
AXIS_ORDER: Tuple[str, ...] = (
    "replica",
    "data",
    "fsdp",
    "stage",
    "expert",
    "sequence",
    "tensor",
)

# Axes whose collectives are expected to cross slices (ride DCN).
DCN_AXES: Tuple[str, ...] = ("replica",)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape. -1 on at most one axis means "absorb the
    remaining devices" (like numpy reshape).

    Examples::

        MeshSpec(data=-1)                       # pure DP over all chips
        MeshSpec(fsdp=-1)                       # pure FSDP
        MeshSpec(data=2, fsdp=2, tensor=2)      # 3D hybrid on 8 chips
        MeshSpec(replica=2, fsdp=-1)            # 2 slices DP over DCN
    """

    replica: int = 1
    data: int = 1
    fsdp: int = 1
    stage: int = 1
    expert: int = 1
    sequence: int = 1
    tensor: int = 1

    def sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolve(self, n_devices: int) -> "MeshSpec":
        """Fill in a single -1 axis so the product equals n_devices."""
        sizes = self.sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"At most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        else:
            if fixed != n_devices:
                raise ValueError(
                    f"MeshSpec product {fixed} != device count {n_devices}"
                )
        return MeshSpec(**sizes)

    @property
    def num_devices(self) -> int:
        p = math.prod(self.sizes().values())
        if p < 0:
            raise ValueError("resolve() the spec first")
        return p


def build_mesh(
    spec: MeshSpec,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a `jax.sharding.Mesh` from a MeshSpec.

    On TPU `mesh_utils.create_device_mesh` lines the physical ICI
    topology (2D/3D torus) up with the logical axes — the difference
    between collectives at full ICI bandwidth and collectives that hop —
    and its failure is an error. Host/CPU device sets have no topology
    and are reshaped in order.
    """
    devices = list(devices if devices is not None else jax.devices())
    sizes = spec.sizes()
    if -1 not in sizes.values():
        need = math.prod(sizes.values())
        if need < len(devices):  # fully-specified spec may use a device subset
            devices = devices[:need]
    spec = spec.resolve(len(devices))
    shape = tuple(spec.sizes()[a] for a in AXIS_ORDER)
    if len(devices) > 1 and devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    """A 1-device mesh with the full axis set (all sizes 1) so sharded
    code paths run unmodified on one chip."""
    device = device or jax.devices()[0]
    return build_mesh(MeshSpec(), [device])


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1)


def flat_axes(mesh: Mesh, *axes: str) -> List[str]:
    """The subset of `axes` with size > 1 in this mesh (useful for
    building minimal PartitionSpecs)."""
    return [a for a in axes if mesh_axis_size(mesh, a) > 1]
