"""Own device time of the operations under the ``lm_head`` scope (the
block's rows against the head and their float32 logits), per ``jit__decode``
call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("lm_head",))
