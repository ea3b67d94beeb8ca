"""Who may open the chip is the raylet's decision, not the caller's: a worker
without a TPU in its lease is pinned to the CPU, and a worker with one gets
a process of its own that starts only when the previous holder is gone."""

import ctypes
import os
import signal
import threading

import pytest

import ray_tpu
from ray_tpu.util.placement_group import (placement_group,
                                          remove_placement_group)
from ray_tpu.util.scheduling_strategies import (
    PlacementGroupSchedulingStrategy)


def _jax_view():
    import jax
    from jax._src import xla_bridge
    platforms = sorted({d.platform for d in jax.devices()})
    return {"env": os.environ.get("JAX_PLATFORMS"), "platforms": platforms,
            "tried": sorted(set(xla_bridge._backends)
                            | set(xla_bridge._backend_errors)),
            "pid": os.getpid()}


def test_worker_without_tpu_lease_sees_only_the_cpu(monkeypatch):
    """JAX_PLATFORMS unset in the driver, as on a machine with a chip:
    a task worker and an actor leased no TPU never try another backend
    (here trying would fail quietly and fall back, so the view of what was
    tried is the evidence), and the driver initialises none at all."""
    monkeypatch.delenv("JAX_PLATFORMS")
    ray_tpu.init(num_cpus=2)
    try:
        class Probe:
            def view(self):
                return _jax_view()

        task = ray_tpu.get(ray_tpu.remote(_jax_view).remote(), timeout=120)
        probe = ray_tpu.remote(Probe).remote()
        actor = ray_tpu.get(probe.view.remote(), timeout=120)
        for view in (task, actor):
            assert view["env"] == "cpu"
            assert view["platforms"] == ["cpu"]
            assert view["tried"] == ["cpu"]
    finally:
        ray_tpu.shutdown()
    from ray_tpu._private import jaxutil
    assert "tpu" not in jaxutil.initialized_backends()


@pytest.fixture
def one_fake_chip():
    ray_tpu.init(num_cpus=4, resources={"TPU": 1})
    yield
    ray_tpu.shutdown()


def test_tpu_lease_is_never_served_from_the_pool(one_fake_chip):
    @ray_tpu.remote(num_tpus=1)
    def leased():
        return os.getpid()

    @ray_tpu.remote
    def pooled():
        return os.getpid()

    first = ray_tpu.get(leased.remote(), timeout=120)
    second = ray_tpu.get(leased.remote(), timeout=120)
    others = set(ray_tpu.get([pooled.remote() for _ in range(4)],
                             timeout=120))
    assert first != second, "a worker that held the chip was leased again"
    assert not {first, second} & others


def test_next_tpu_worker_waits_for_the_holders_exit(one_fake_chip):
    """Removing a gang's placement group returns its TPU at once, while the
    killed worker may take its time to exit (JaxTrainer ends so).  The next
    worker that is leased the chip must not start before it has."""
    class Holder:
        def outlive_the_kill_by(self, seconds):
            # SIG_IGN through libc: Python sets handlers only from the main
            # thread, which an actor's methods do not run on.
            ctypes.CDLL(None).signal(signal.SIGTERM, ctypes.c_void_p(1))
            threading.Timer(seconds, os._exit, (0,)).start()
            return os.getpid()

    class Successor:
        def __init__(self, holder_pid):
            self.holder_alive_at_start = os.path.exists(f"/proc/{holder_pid}")

        def check(self):
            return self.holder_alive_at_start

    pg = placement_group([{"CPU": 1, "TPU": 1}])
    assert pg.ready(timeout=60)
    holder = ray_tpu.remote(Holder).options(
        num_tpus=1, scheduling_strategy=PlacementGroupSchedulingStrategy(
            pg, placement_group_bundle_index=0)).remote()
    pid = ray_tpu.get(holder.outlive_the_kill_by.remote(1.5), timeout=120)
    ray_tpu.kill(holder)
    remove_placement_group(pg)
    successor = ray_tpu.remote(Successor).options(num_tpus=1).remote(pid)
    assert ray_tpu.get(successor.check.remote(), timeout=120) is False
