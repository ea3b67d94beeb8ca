"""Own device time of the operations under the ``linear_state`` scope (the
gated delta rule on the slots' state rows: the decay, the two sums, the
delta's outer product and the write-back; q and k's normalisation with
them), per ``jit__decode`` call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("linear_state",))
