"""Own device time of the operations under the ``latent_append`` and
``latent_read`` scopes (the scatter of a position's latent row into the
pool; the gather of the sequences' latent pages, the scores, the softmax and
the weighing of the rows), per ``jit__decode`` call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("latent_append",
                                               "latent_read"))
