"""Own device time of the operations under the ``linear_conv`` scope (the
width-4 causal convolution over a slot's kept inputs, its SiLU and the
tail's write-back), per ``jit__decode`` call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("linear_conv",))
