"""ray_tpu.ops: TPU kernels (Pallas) and sharded attention primitives.

New capability vs. the reference (SURVEY §5.7: no sequence/context
parallelism exists in Ray): flash attention as a Pallas TPU kernel, ring
attention over the `sp` mesh axis, and a Ulysses-style all-to-all
alternative.  Everything here runs on the CPU backend too (Pallas interpret
mode / plain lax), so the test suite exercises it on the virtual 8-device
mesh.
"""

from ray_tpu.ops.flash_attention import flash_attention  # noqa: F401
from ray_tpu.ops.paged_attention import (  # noqa: F401
    append_kv,
    paged_attention,
    prefill_kv,
)
from ray_tpu.ops.ring_attention import (  # noqa: F401
    ring_attention,
    ring_attention_sharded,
    ulysses_attention,
)
