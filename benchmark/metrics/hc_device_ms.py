"""Own device time of the operations under the ``hc_coeff`` and ``hc_mix``
scopes (a hyper-connection's wide norm, projection, sigmoids and Sinkhorn
rounds; the read of the stream's rows, their mixing and the write of the
sublayer's output into them), per ``jit__decode`` call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("hc_coeff", "hc_mix"))
