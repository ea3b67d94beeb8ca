"""TPU topology discovery and resource shapes.

Replaces the reference's GPU autodetection (`_private/resource_spec.py:287`,
`util/accelerators/accelerators.py` — NVIDIA-only) with TPU-native discovery:
instead of counting CUDA devices we count the chips' device nodes and
read, where available, the TPU environment metadata (generation, slice
topology, worker/host id).  Discovery never imports JAX: the daemon that
runs it must not open the chip its workers will lease.  A node's resource
dict then advertises

    ``TPU``                  — local chip count (schedulable, like "GPU")
    ``TPU-{gen}-head``       — 1.0 on slice host 0 (gang anchor)
    ``tpu-slice:{name}``     — 1.0 per host of a named slice (gang bundles)

so placement groups can gang one actor per host of a slice (STRICT_SPREAD
over ``tpu-slice:*`` bundles) the way the reference gangs one worker per GPU.

Discovery is lazy and never *requires* TPU hardware: on CPU-only machines it
reports zero chips, so every code path stays testable with the virtual
8-device CPU mesh (`XLA_FLAGS=--xla_force_host_platform_device_count=8`).
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

# Known slice shapes (chips per host is 4 for v2-v4; v5e/v5p vary by topology).
_CHIPS_PER_HOST_DEFAULT = 4


@dataclasses.dataclass(frozen=True)
class TpuTopology:
    """Static description of the TPU visible to this host.

    ``generation``      e.g. "v4", "v5e" ("" when no TPU present)
    ``num_local_chips`` chips attached to this host
    ``num_slice_hosts`` hosts in the slice this host belongs to
    ``host_index``      this host's index within the slice
    ``slice_name``      stable identifier for the slice (for gang bundles)
    ``mesh_shape``      physical chip mesh of the full slice, e.g. (4, 4, 2)
    """

    generation: str = ""
    num_local_chips: int = 0
    num_slice_hosts: int = 1
    host_index: int = 0
    slice_name: str = ""
    mesh_shape: Tuple[int, ...] = ()

    @property
    def total_chips(self) -> int:
        return self.num_local_chips * self.num_slice_hosts

    def resource_dict(self) -> Dict[str, float]:
        """Resources this host should advertise to the raylet."""
        if self.num_local_chips == 0:
            return {}
        res: Dict[str, float] = {"TPU": float(self.num_local_chips)}
        if self.generation:
            # accelerator_type constraint resource (reference:
            # util/accelerators + resource "accelerator_type:<T>"):
            # tasks declaring accelerator_type="v5e" request a sliver.
            res[f"accelerator_type:{self.generation}"] = \
                float(self.num_local_chips)
        if self.slice_name:
            res[f"tpu-slice:{self.slice_name}"] = 1.0
        if self.host_index == 0 and self.generation:
            res[f"TPU-{self.generation}-head"] = 1.0
        return res


def _detect_from_env() -> Optional[TpuTopology]:
    """Cloud TPU VM metadata via env (TPU_WORKER_ID etc.), if present."""
    accel = os.environ.get("TPU_ACCELERATOR_TYPE")  # e.g. "v4-32"
    if not accel:
        return None
    gen = accel.split("-")[0]
    try:
        total = int(accel.split("-")[1])
    except (IndexError, ValueError):
        total = _CHIPS_PER_HOST_DEFAULT
    try:
        chips_per_host = int(
            os.environ.get("TPU_CHIPS_PER_HOST") or _CHIPS_PER_HOST_DEFAULT)
    except ValueError:
        chips_per_host = _CHIPS_PER_HOST_DEFAULT
    chips_per_host = max(1, chips_per_host)
    # v2/v3/v4/v5p accelerator types count TensorCores (2 per chip): N//2
    # chips.  v5e/v6e (litepod) count chips directly.
    num_chips = total // 2 if gen in ("v2", "v3", "v4", "v5p") else total
    hosts = max(1, num_chips // chips_per_host)
    try:
        host_index = int(os.environ.get("TPU_WORKER_ID") or 0)
    except ValueError:
        host_index = 0
    return TpuTopology(
        generation=gen,
        num_local_chips=min(num_chips, chips_per_host),
        num_slice_hosts=hosts,
        host_index=host_index,
        slice_name=os.environ.get("TPU_NAME", accel),
        mesh_shape=(num_chips,),
    )


def local_chip_count() -> int:
    """Chips attached to this host, counted from their device nodes (one
    ``/dev/accel<N>`` or one vfio group ``/dev/vfio/<N>`` each) without
    opening them.  ``/dev/vfio/vfio`` is the container's control node, not
    a chip.  ``RT_NUM_TPU_CHIPS`` overrides the count."""
    env = os.environ.get("RT_NUM_TPU_CHIPS")
    if env:
        return int(env)
    return len(glob.glob("/dev/accel[0-9]*")
               or glob.glob("/dev/vfio/[0-9]*"))


_cached: Optional[TpuTopology] = None


def detect(force: bool = False) -> TpuTopology:
    """Detect the local TPU topology (cached).  The env metadata names the
    generation and the slice; the chip count is what is attached — a
    sandbox may expose one chip of a host whose metadata says four."""
    global _cached
    if _cached is None or force:
        _cached = dataclasses.replace(
            _detect_from_env() or TpuTopology(),
            num_local_chips=local_chip_count())
    return _cached


def slice_bundle_shapes(topo: TpuTopology) -> List[Dict[str, float]]:
    """Placement-group bundles that gang-reserve one slot per slice host.

    Used by the Train backend: ``placement_group(slice_bundle_shapes(t),
    strategy="STRICT_SPREAD")`` pins one worker actor to each host of the
    slice (reference analogue: BackendExecutor PG creation,
    `train/_internal/backend_executor.py:138`).
    """
    if topo.num_local_chips == 0:
        return [{"CPU": 1.0}]
    return [
        {"TPU": float(topo.num_local_chips)}
        for _ in range(topo.num_slice_hosts)
    ]
