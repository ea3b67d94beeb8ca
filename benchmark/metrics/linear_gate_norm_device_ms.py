"""Own device time of the operations under the ``linear_gate_norm`` scope
(every head's values RMS-normed and gated by ``silu(z)``), per
``jit__decode`` call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("linear_gate_norm",))
