"""Own device time of the operations under the ``kda_gate`` scope (Kimi
Delta Attention's gates: the decay's two low-rank products, its softplus
and the product with ``-exp(A_log)``, and beta's projection and sigmoid; the
decay's exponential is taken where the state is stepped, under
``linear_state``), per ``jit__decode`` call."""

from benchmark import decode_scopes


def read(run):
    return decode_scopes.decode_scope_ms(run, ("kda_gate",))
