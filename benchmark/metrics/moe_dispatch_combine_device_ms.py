"""Own device time of the operations under the ``moe_dispatch`` and
``moe_combine`` scopes (sort by expert and gather; gather back and the
gate-weighted sum) inside the decode programs, per ``jit__decode`` call."""

from benchmark import moe_scopes


def read(run):
    return moe_scopes.decode_scope_ms(run, ("moe_dispatch", "moe_combine"))
