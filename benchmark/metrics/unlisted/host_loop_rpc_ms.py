"""Per ``jit__decode`` call, the transport's synchronous work on the actor's
loop thread: frames packed and written, frames parsed and their messages
handed on (``rpc_out_us + rpc_in_us`` of ``rt:engine.decode.dispatch``).  One
of the four parts of ``host_loop_cpu_ms`` (``benchmark/loop_split.py``)."""

from benchmark import loop_split


def read(run):
    return loop_split.part_ms(run, "rpc")
