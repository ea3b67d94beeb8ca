"""Per ``jit__decode`` call, the CPU time of the actor's loop thread while
the exec thread was inside ``rt:engine.decode.dispatch``: what the loop ran
against it under the same GIL (``dispatch_loop_cpu_us`` of
``rt:engine.decode.fetch``)."""

from benchmark import host_threads


def read(run):
    return host_threads.per_decode_call_ms(run, host_threads.total_us(
        run, host_threads.FETCH, "dispatch_loop_cpu_us"))
