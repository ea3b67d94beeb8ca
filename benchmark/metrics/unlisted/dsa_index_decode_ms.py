"""Own device time of the operations under the ``dsa_index`` scope inside the
``jit__decode`` programs, per decode step (``benchmark/decode_scopes.py``)."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("dsa_index",))
