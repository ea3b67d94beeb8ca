"""Multi-device RL learner plumbing: dp-mesh sharded policy updates.

Design analog: reference ``rllib/execution/multi_gpu_learner_thread.py:20``
and ``rllib/core/rl_trainer/trainer_runner.py:21`` — N learner GPUs, each
loading a batch shard, gradients allreduced by NCCL, one weight copy
broadcast back to rollout workers.

TPU-first delta: there is no learner *thread pool* — the learner is ONE
``shard_map`` program over a ``jax.sharding.Mesh``.  Each device receives
its shard of the train batch (``PartitionSpec("dp")`` on the leading axis),
runs the same minibatch-SGD/V-trace scan on it, and gradients are
``lax.pmean``-ed over the mesh axis inside jit, so XLA emits the
all-reduce on ICI exactly where NCCL would run.  Params/optimizer state
stay replicated (RL policy nets are KB–MB scale; batch, not params, is
what needs scaling out — fsdp would add collectives for no memory win).
Rollout workers remain host-CPU actors; weight broadcast reuses
``WorkerSet.sync_weights``.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

import jax

DP_AXIS = "dp"


def learner_mesh(num_devices: Optional[int] = None) -> "jax.sharding.Mesh":
    """A 1-D ("dp",) mesh over the first ``num_devices`` local devices."""
    devs = jax.devices()
    n = num_devices or len(devs)
    if n > len(devs):
        raise ValueError(
            f"num_learner_devices={n} but only {len(devs)} devices visible")
    return jax.sharding.Mesh(np.asarray(devs[:n]), (DP_AXIS,))


def shard_update(update_fn, mesh, n_state_outputs: int = 2):
    """Wrap a per-shard ``update_fn(params, opt_state, *rest, batch)`` into
    a jitted shard_map over ``mesh``: everything replicated except the
    trailing ``batch`` arg, whose pytree leaves shard on dim 0 over dp.

    ``update_fn`` must pmean its grads/stats over ``DP_AXIS`` itself (the
    policy closures do), which keeps the replicated outputs consistent.
    """
    P = jax.sharding.PartitionSpec

    def wrapped(*args):
        n_in = len(args)
        in_specs = tuple([P()] * (n_in - 1) + [P(DP_AXIS)])
        out_specs = tuple([P()] * (n_state_outputs + 1))
        return jax.shard_map(update_fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    return jax.jit(wrapped)


def trim_batch(batch: Dict[str, np.ndarray], multiple: int
               ) -> Dict[str, np.ndarray]:
    """Trim every leading dim to a multiple of the mesh size so shards are
    equal (dropping <multiple trailing rows, same as the reference's
    per-GPU loader truncation)."""
    if multiple <= 1:
        return batch
    n = next(iter(batch.values())).shape[0]
    keep = (n // multiple) * multiple
    if keep == n:
        return batch
    if keep == 0:
        raise ValueError(f"batch of {n} rows cannot shard over "
                         f"{multiple} learner devices")
    return {k: v[:keep] for k, v in batch.items()}
