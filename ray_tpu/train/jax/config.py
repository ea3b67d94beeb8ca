"""JAX distributed backend: the TPU-native process-group setup.

Design analog: reference ``python/ray/train/torch/config.py`` --
_TorchBackend.on_start:132 -> _setup_torch_process_group:69 ->
dist.init_process_group(nccl):113.  TPU replacement: rank 0 publishes a
coordinator address; every worker calls ``jax.distributed.initialize`` so
the gang becomes one multi-controller JAX program.  After that, in-slice
collectives are *compiled into* the pjit step over ICI -- there is no NCCL
ring to manage at runtime.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Optional

from ray_tpu.train.backend import Backend, BackendConfig

logger = logging.getLogger(__name__)


@dataclass
class JaxConfig(BackendConfig):
    """distributed: None = auto (initialize when num_workers > 1).
    platform: override JAX_PLATFORMS in workers ("tpu", "cpu")."""

    distributed: Optional[bool] = None
    platform: Optional[str] = None
    coordinator_port: Optional[int] = None

    def backend_cls(self):
        return _JaxBackend


def _init_jax_distributed(coordinator: str, num_processes: int,
                          process_id: int, platform: Optional[str]):
    import jax
    if platform:
        # jax may already be imported in this worker (it reads the env
        # only then); the config wins until a backend is initialized.
        jax.config.update("jax_platforms", platform)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return len(jax.devices())


def _shutdown_jax_distributed():
    import jax
    try:
        jax.distributed.shutdown()
    except Exception:
        pass


class _JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig):
        n = len(worker_group)
        distributed = backend_config.distributed
        if distributed is None:
            distributed = n > 1
        if not distributed:
            if backend_config.platform:
                worker_group.execute(
                    _set_platform, backend_config.platform)
            return
        # Rank 0 owns the coordinator (reference: rank-0 addr/port handshake
        # at train/torch/config.py:137-141).
        ip = worker_group.workers[0].ip
        port = backend_config.coordinator_port or \
            worker_group.execute_single(0, _free_port)
        coordinator = f"{ip}:{port}"
        logger.info("jax.distributed coordinator at %s (%d processes)",
                    coordinator, n)
        import ray_tpu
        refs = [
            w.actor.execute.remote(_init_jax_distributed, coordinator, n,
                                   w.rank, backend_config.platform)
            for w in worker_group.workers
        ]
        device_counts = ray_tpu.get(refs, timeout=120.0)
        logger.info("jax.distributed up: global devices per proc %s",
                    device_counts)

    def on_shutdown(self, worker_group, backend_config: JaxConfig):
        if len(worker_group) > 1 and backend_config.distributed is not False:
            try:
                worker_group.execute(_shutdown_jax_distributed)
            except Exception:
                pass


def _set_platform(platform: str):
    os.environ["JAX_PLATFORMS"] = platform


def _free_port() -> int:
    import socket
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port
