"""Per ``jit__decode`` call, the streams' synchronous work on the actor's
loop thread: a yield's value stored and, once its ack is back, its reference
and borrow made (``stream_store_us + stream_after_us`` of
``rt:engine.decode.dispatch``).  One of the four parts of
``host_loop_cpu_ms`` (``benchmark/loop_split.py``)."""

from benchmark import loop_split


def read(run):
    return loop_split.part_ms(run, "stream")
