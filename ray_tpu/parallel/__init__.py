"""ray_tpu.parallel: mesh construction, sharding rules, and collectives.

This package is the TPU-native replacement for the reference's entire
communication/parallelism stack (`ray.util.collective` NCCL groups,
`util/collective/collective.py:120-615`; torch DDP/FSDP wrapping,
`train/torch/train_loop_utils.py:24-74`).  On TPU, parallelism is not a
runtime library but a *compilation strategy*: you pick a `jax.sharding.Mesh`
over the slice, annotate array shardings, and XLA emits the ICI collectives
inside the step function (all but one: tp's activation sums, which
`collectives.py` writes out as chunks that travel beside the projections).
The classes here make that recipe declarative:

    spec = MeshSpec(dp=2, fsdp=2, tp=2)        # 8 chips
    mesh = spec.build()
    rules = LogicalAxisRules.for_transformer(spec)
    train_step = jit_with_shardings(step_fn, mesh, rules, ...)

Axes (any may be 1 / absent):
    dp    data parallel           — batch sharding, gradient psum
    fsdp  fully-sharded DP (ZeRO) — batch + parameter sharding on one axis
    tp    tensor parallel         — hidden/heads sharding (Megatron layout)
    pp    pipeline parallel       — layer-stage sharding via shard_map loop
    sp    sequence/context        — sequence-axis sharding (ring attention)
    ep    expert parallel         — MoE expert sharding, all-to-all dispatch
"""

from ray_tpu.parallel.mesh import (  # noqa: F401
    MeshSpec,
    make_mesh,
    mesh_shape_for_devices,
)
from ray_tpu.parallel.sharding import (  # noqa: F401
    LogicalAxisRules,
    init_sharded,
    logical_sharding,
    shard_params,
    with_logical_constraint,
)
from ray_tpu.parallel.multislice import (  # noqa: F401
    assert_slice_aligned,
    dcn_axes,
    ici_axes,
    slice_mesh,
)
from ray_tpu.parallel.collectives import (  # noqa: F401
    all_gather,
    all_reduce,
    all_to_all,
    barrier_sum,
    ppermute_ring,
    psum_scatter,
)
