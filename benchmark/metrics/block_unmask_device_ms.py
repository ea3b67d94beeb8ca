"""Own device time of the operations under the ``block_unmask`` scope (the
softmax's value at the chosen logit, the ranking, the state's update), per
``jit__decode`` call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("block_unmask",))
