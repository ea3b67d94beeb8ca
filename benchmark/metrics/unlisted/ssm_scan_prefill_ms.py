"""Own device time of the state-space rule's chunked form and the write of
the slot's state rows (the scope ``linear_state`` of the Mamba mixers) inside
the ``jit__prefill`` programs, per prefill call
(``benchmark/prefill_scopes.py``).  Over ``prefill_device_ms`` it is the
rule's share of a prefill."""

from benchmark import prefill_scopes


def read(run):
    return prefill_scopes.prefill_scope_ms(run, ("linear_state",))
