"""Per ``jit__decode`` call, what is left of ``host_loop_cpu_ms`` after the
engine's, the streams' and the transport's parts, signed: the event loop
itself, the generators' frames, the collector, the GIL
(``benchmark/loop_split.py``)."""

from benchmark import loop_split


def read(run):
    return loop_split.part_ms(run, "unnamed")
