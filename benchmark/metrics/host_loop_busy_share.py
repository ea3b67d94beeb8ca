"""How full the replica's loop thread is: its CPU time over the wall time
between decode steps' submissions (``step_loop_cpu_us`` over ``step_us`` of
``rt:engine.decode.dispatch``, summed over the window)."""

from benchmark import host_threads


def read(run):
    return host_threads.share(
        run, (host_threads.DISPATCH, "step_loop_cpu_us"),
        (host_threads.DISPATCH, "step_us"))
