"""Per ``jit__decode`` call, the CPU time of the actor's loop thread between
the exec thread's return and the engine's task running again
(``resume_loop_cpu_us`` of ``rt:engine.deliver``): near the crossing's wall
time (``resume_us``) the loop was busy with other coroutines, near 0 it
slept until woken."""

from benchmark import host_threads


def read(run):
    return host_threads.per_decode_call_ms(run, host_threads.total_us(
        run, host_threads.DELIVER, "resume_loop_cpu_us"))
