"""Own device time of the operations under the ``dsa_select`` scope inside the
``jit__prefill`` programs, per call (a chunk of a prompt;
``benchmark/prefill_scopes.py``)."""

from benchmark import prefill_scopes


def read(run):
    return prefill_scopes.prefill_scope_ms(run, ("dsa_select",))
