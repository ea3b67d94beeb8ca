"""Per ``jit__decode`` call, the time the exec thread was inside
``rt:engine.decode.dispatch`` and NOT running (waiting for the GIL or for a
lock of the runtime): ``dispatch_us - dispatch_cpu_us``, wall less the
thread's own CPU clock, of the ``rt:engine.decode.fetch`` that follows."""

from benchmark import host_threads


def read(run):
    return host_threads.per_decode_call_ms(run, host_threads.total_us(
        run, host_threads.FETCH, "dispatch_us", less="dispatch_cpu_us"))
