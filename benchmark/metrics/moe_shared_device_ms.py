"""Own device time of the operations under the ``moe_shared`` scope (the
shared expert's SwiGLU, which every token goes through), per ``jit__decode``
call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("moe_shared",))
