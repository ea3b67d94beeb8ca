"""Own device time of ``moe_dispatch`` (the sort by expert and the gather of
the rows, twice over for gate and up) and ``moe_combine`` (back to token
order and the gate-weighted sum) inside the ``jit__prefill`` programs, per
prefill call: at 8,192-16,384 assignments of 2,048 values these copies are
tens of megabytes a layer."""

from benchmark import prefill_scopes


def read(run):
    return prefill_scopes.prefill_scope_ms(
        run, ("moe_dispatch", "moe_combine"))
