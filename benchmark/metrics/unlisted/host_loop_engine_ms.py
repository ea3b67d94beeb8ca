"""Per ``jit__decode`` call, the engine's own synchronous work on the actor's
loop thread: the lengths of ``rt:engine.deliver`` (``_push``, ``_retire``) and
``rt:engine.schedule`` (``_sweep``, ``_admit``, ``_decode_inputs``).  One of
the four parts of ``host_loop_cpu_ms`` (``benchmark/loop_split.py``)."""

from benchmark import loop_split


def read(run):
    return loop_split.part_ms(run, "engine")
