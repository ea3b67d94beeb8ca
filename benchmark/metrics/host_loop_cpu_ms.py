"""Per ``jit__decode`` call, the CPU time of the actor's loop thread from one
decode step's submission to the next (``step_loop_cpu_us`` of
``rt:engine.decode.dispatch``): everything a step costs the loop: delivery,
the streams' fan-out, schedule, the prefills between."""

from benchmark import host_threads


def read(run):
    return host_threads.per_decode_call_ms(run, host_threads.total_us(
        run, host_threads.DISPATCH, "step_loop_cpu_us"))
