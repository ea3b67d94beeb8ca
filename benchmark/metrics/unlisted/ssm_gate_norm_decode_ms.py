"""Own device time of the Mamba mixers' gate and the one norm over all
channels that follows it (the scope ``linear_gate_norm``) inside the
``jit__decode`` programs, per decode step (``benchmark/decode_scopes.py``)."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("linear_gate_norm",))
