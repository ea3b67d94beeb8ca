"""Own device time of the operations under the ``mla_absorb`` scope (the two
products with ``wkv_b`` that stand in for expanding keys and values: its key
half into the query, its value half after the attention), per
``jit__decode`` call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("mla_absorb",))
