"""Own device time of the state-space rule's token step (everything under
the scope ``linear_state`` of the Mamba mixers: the kernel that reads and
writes every live slot's state rows, the spread of the step's operands over
the panels, the skip) inside the ``jit__decode`` programs, per decode step
(``benchmark/decode_scopes.py``).  Over ``decode_device_ms`` it is the
states' share of a step."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("linear_state",))
