"""Own device time of the Mamba mixers' short convolution with its bias and
SiLU and the tail's hand-over (the scope ``linear_conv``) inside the
``jit__decode`` programs, per decode step (``benchmark/decode_scopes.py``)."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("linear_conv",))
