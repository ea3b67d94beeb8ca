"""Own device time of everything under ``moe_experts`` (the two grouped
matmuls and the SwiGLU between them) inside the ``jit__prefill`` programs,
per prefill call: the experts at prefill rows (128-256 rows an expert at
LFM2's 64 experts top-4), which ``moe_experts_device_ms`` (decode steps
alone) does not see."""

from benchmark import prefill_scopes


def read(run):
    return prefill_scopes.prefill_scope_ms(run, ("moe_experts",))
