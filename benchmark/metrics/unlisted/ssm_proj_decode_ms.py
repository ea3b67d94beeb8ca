"""Own device time of the Mamba mixers' two projections (``z | xBC | dt``
in, and out: the scope ``ssm_proj``) inside the ``jit__decode`` programs, per
decode step (``benchmark/decode_scopes.py``)."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("ssm_proj",))
