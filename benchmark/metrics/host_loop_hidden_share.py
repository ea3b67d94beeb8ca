"""The share of the loop thread's work that ran while the device did: its
CPU time inside the fetch phase (``fetch_loop_cpu_us`` of
``rt:engine.deliver``) over its CPU time over whole steps
(``step_loop_cpu_us`` of ``rt:engine.decode.dispatch``)."""

from benchmark import host_threads


def read(run):
    return host_threads.share(
        run, (host_threads.DELIVER, "fetch_loop_cpu_us"),
        (host_threads.DISPATCH, "step_loop_cpu_us"))
