"""TPU-VM node provider: slice-atomic provisioning against a cloud API.

Design analog: reference ``python/ray/autoscaler/_private/gcp/node_provider
.py`` (GCPNodeProvider: API-backed create/terminate with operation polling)
— reshaped for TPU pods, where the provisioning unit is a SLICE (a gang of
hosts sharing ICI), not an instance:

  * slice atomicity — a v4-32 slice is 4 hosts that exist together or not
    at all; a partially-created slice is torn down, never surfaced.
  * async provisioning — the cloud API returns long-running operations;
    the provider polls them off the autoscaler's critical path and
    surfaces nodes only when the whole slice is READY.
  * error classes — QUOTA/CAPACITY errors (common for TPU pools) are
    retried with backoff up to a budget; permanent errors mark the launch
    failed so the autoscaler's demand loop can pick a different shape.

The cloud API is injected (``TpuApi`` protocol) so the provisioning state
machine is fully testable without GCP: tests drive it with a fake API that
injects capacity errors and partial-slice failures.  Wiring an actual GCP
client is a deployment concern (create_node/delete_node/get_operation are
1:1 with the TPU VM REST verbs).
"""

from __future__ import annotations

import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ray_tpu.autoscaler.node_provider import (NODE_TYPE_LABEL, NodeProvider,
                                              NodeTypeConfig, ProviderNode)

# operation states reported by TpuApi.get_operation
PENDING, READY, FAILED = "PENDING", "READY", "FAILED"


class TpuCapacityError(RuntimeError):
    """Transient: no capacity / quota right now — retry with backoff."""


class TpuApi:
    """Injected cloud surface (1:1 with the TPU-VM REST verbs)."""

    def create_slice(self, accelerator_type: str, hosts: int,
                     labels: Dict[str, str]) -> str:
        """Begin creating one slice (all its hosts); returns operation id.
        Raises TpuCapacityError when the pool has no capacity."""
        raise NotImplementedError

    def get_operation(self, op_id: str) -> Dict:
        """{"state": PENDING|READY|FAILED, "hosts": [host_id, ...],
        "error": str|None}.  READY means EVERY host of the slice is up."""
        raise NotImplementedError

    def delete_slice(self, slice_id: str) -> None:
        raise NotImplementedError


@dataclass
class _Launch:
    op_id: str
    node_type: str
    labels: Dict[str, str]
    attempts: int = 0
    next_poll: float = 0.0
    # retry bookkeeping for capacity-failed creates (op_id == "")
    accel: str = ""
    hosts: int = 1


@dataclass
class _Slice:
    slice_id: str
    node_type: str
    hosts: List[str]
    labels: Dict[str, str] = field(default_factory=dict)


class TPUVMNodeProvider(NodeProvider):
    """Slice-atomic async provider over an injected TpuApi."""

    def __init__(self, api: TpuApi, *,
                 accelerator_types: Optional[Dict[str, str]] = None,
                 max_create_retries: int = 5,
                 retry_backoff_s: float = 2.0):
        self._api = api
        self._accel = accelerator_types or {}
        self._max_retries = max_create_retries
        self._backoff = retry_backoff_s
        self._lock = threading.Lock()
        self._slices: Dict[str, _Slice] = {}
        self._launches: List[_Launch] = []
        self.failed_launches: List[Dict] = []   # surfaced to the monitor

    # -- NodeProvider surface --------------------------------------------

    def non_terminated_nodes(self) -> List[ProviderNode]:
        self._poll_launches()
        with self._lock:
            out = []
            for s in self._slices.values():
                for h in s.hosts:
                    out.append(ProviderNode(node_id=h,
                                            node_type=s.node_type,
                                            labels=dict(s.labels)))
            return out

    def create_node(self, node_type: NodeTypeConfig, count: int,
                    labels: Optional[Dict[str, str]] = None) -> List[str]:
        """Begin `count` slice launches; returns operation ids (nodes
        surface via non_terminated_nodes once their slice is READY)."""
        labels = {**(labels or {}), NODE_TYPE_LABEL: node_type.name}
        accel = self._accel.get(node_type.name, node_type.name)
        hosts = max(1, int(node_type.resources.get("hosts", 1)))
        ops = []
        for _ in range(count):
            op = self._begin_launch(accel, hosts, node_type.name, labels)
            if op is not None:
                ops.append(op)
        return ops

    def terminate_node(self, node_id: str) -> None:
        with self._lock:
            for sid, s in self._slices.items():
                if node_id in s.hosts:
                    break
            else:
                return
        # Terminating ANY host tears down the whole slice — a slice with a
        # missing host is not a smaller slice, it's a broken one (no ICI
        # wraparound).  Delete FIRST, untrack after: a failed delete must
        # leave the slice visible so it can be re-terminated, not orphan a
        # live (billing) slice.
        self._api.delete_slice(sid)
        with self._lock:
            self._slices.pop(sid, None)

    # -- provisioning state machine --------------------------------------

    def _begin_launch(self, accel, hosts, type_name, labels,
                      attempts: int = 0) -> Optional[str]:
        try:
            op_id = self._api.create_slice(accel, hosts, labels)
        except TpuCapacityError as e:
            if attempts >= self._max_retries:
                self.failed_launches.append(
                    {"node_type": type_name, "error": str(e)})
                return None
            with self._lock:
                self._launches.append(_Launch(
                    op_id="", node_type=type_name, labels=labels,
                    attempts=attempts + 1,
                    next_poll=time.monotonic() +
                    self._backoff * (2 ** attempts),
                    accel=accel, hosts=hosts))
            return None
        with self._lock:
            self._launches.append(_Launch(op_id=op_id, node_type=type_name,
                                          labels=labels, attempts=attempts))
        return op_id

    def _poll_launches(self) -> None:
        now = time.monotonic()
        with self._lock:
            launches, self._launches = self._launches, []
        for ln in launches:
            if ln.op_id == "":
                # a backoff-scheduled retry of a capacity failure
                if now >= ln.next_poll:
                    self._begin_launch(ln.accel, ln.hosts, ln.node_type,
                                       ln.labels, attempts=ln.attempts)
                else:
                    with self._lock:
                        self._launches.append(ln)
                continue
            op = self._api.get_operation(ln.op_id)
            if op["state"] == PENDING:
                with self._lock:
                    self._launches.append(ln)
            elif op["state"] == READY:
                with self._lock:
                    self._slices[ln.op_id] = _Slice(
                        slice_id=ln.op_id, node_type=ln.node_type,
                        hosts=list(op["hosts"]), labels=ln.labels)
            else:  # FAILED — tear down any partially-created hosts
                try:
                    self._api.delete_slice(ln.op_id)
                except Exception:
                    pass
                self.failed_launches.append(
                    {"node_type": ln.node_type,
                     "error": op.get("error") or "operation failed"})
