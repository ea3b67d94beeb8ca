"""Own device time of the gated short convolution's three scopes
(``conv_in``, ``conv_mix``, ``conv_out``) inside the ``jit__decode``
programs, per decode step (``benchmark/decode_scopes.py``)."""

from benchmark import decode_scopes

SCOPES = ("conv_in", "conv_mix", "conv_out")


def read(run):
    return decode_scopes.decode_scope_ms(run, SCOPES)
