"""Own device time of the operations under the ``moe_experts`` scope (the
cast of the expert weights, the grouped matmuls, SwiGLU) inside the decode
programs, per ``jit__decode`` call."""

from benchmark import moe_scopes


def read(run):
    return moe_scopes.decode_scope_ms(run, ("moe_experts",))
