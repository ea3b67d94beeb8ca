"""Own device time of the operations under the ``moe_router`` scope (router
product, softmax, top-k) inside the decode programs, per ``jit__decode``
call."""

from benchmark import moe_scopes


def read(run):
    return moe_scopes.decode_scope_ms(run, ("moe_router",))
