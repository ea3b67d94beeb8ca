"""Mesh construction over TPU slices.

Reference analogue: NCCL communicator setup (`util/collective/collective_group/
nccl_collective_group.py:127`) and torch process-group init (`train/torch/
config.py:69-113`).  On TPU neither exists: the `jax.sharding.Mesh` *is* the
communicator, and XLA compiles the collectives.  The only real design work is
axis ordering — axes that carry the most traffic (tp, sp) must map to the
fastest ICI dimension, while dp/pp can ride the slower outer dimensions or
DCN.  `MeshSpec` encodes that ordering convention once.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# Innermost-first: highest-bandwidth-need axes placed on contiguous devices.
# mesh_utils.create_device_mesh puts the *last* mesh dims on nearest neighbors,
# so we order axes slowest-traffic-first.
AXIS_ORDER: Tuple[str, ...] = ("dp", "fsdp", "pp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh: sizes for each standard parallelism axis.

    Sizes of 1 are kept in the mesh (zero cost, lets sharding rules be
    written once regardless of which axes are active).
    """

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    @property
    def axis_sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    @property
    def num_devices(self) -> int:
        return math.prod(self.axis_sizes.values())

    @property
    def data_axes(self) -> Tuple[str, ...]:
        """Axes over which the global batch is sharded."""
        return ("dp", "fsdp")

    @property
    def batch_shard_size(self) -> int:
        return self.dp * self.fsdp

    def build(self, devices: Optional[Sequence] = None) -> "jax.sharding.Mesh":
        import jax
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        n = self.num_devices
        if len(devices) < n:
            raise ValueError(
                f"MeshSpec needs {n} devices, only {len(devices)} available")
        devices = list(devices)[:n]
        shape = tuple(self.axis_sizes.values())
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        return Mesh(dev_array, axis_names=tuple(self.axis_sizes.keys()))

    @staticmethod
    def for_devices(n: int, *, tp: int = 1, pp: int = 1, sp: int = 1,
                    ep: int = 1, fsdp: Optional[int] = None) -> "MeshSpec":
        """Fill the remaining device budget with data parallelism."""
        used = tp * pp * sp * ep
        if n % used:
            raise ValueError(f"{n} devices not divisible by tp*pp*sp*ep={used}")
        rest = n // used
        if fsdp is None:
            fsdp, dp = rest, 1
        else:
            if rest % fsdp:
                raise ValueError(f"residual {rest} not divisible by fsdp={fsdp}")
            dp = rest // fsdp
        return MeshSpec(dp=dp, fsdp=fsdp, pp=pp, ep=ep, sp=sp, tp=tp)


def mesh_shape_for_devices(n: int) -> Tuple[int, ...]:
    """Near-square 2D factorization of n (helper for ad-hoc meshes)."""
    a = int(math.sqrt(n))
    while n % a:
        a -= 1
    return (n // a, a)


def make_mesh(axis_sizes: Dict[str, int],
              devices: Optional[Sequence] = None) -> "jax.sharding.Mesh":
    """Build a Mesh from an arbitrary {axis: size} dict (order preserved)."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = math.prod(axis_sizes.values())
    dev_array = np.asarray(list(devices)[:n]).reshape(tuple(axis_sizes.values()))
    return Mesh(dev_array, axis_names=tuple(axis_sizes.keys()))
