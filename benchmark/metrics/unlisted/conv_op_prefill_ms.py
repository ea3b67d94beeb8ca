"""Own device time of the gated short convolution's three scopes
(``conv_in``: the projection to 3D and ``B * z``; ``conv_mix``: the
convolution, ``C *`` and the tail's hand-over; ``conv_out``) inside the
``jit__prefill`` programs, per prefill call (``benchmark/prefill_scopes.py``).
Over ``prefill_device_ms`` it is the operator's share of a prefill."""

from benchmark import prefill_scopes

SCOPES = ("conv_in", "conv_mix", "conv_out")


def read(run):
    return prefill_scopes.prefill_scope_ms(run, SCOPES)
