"""Median time the owner's handler held a yield (``held_us`` of
``rt:stream.yield``): the entry stored, the reference made, the consumer
woken."""

from benchmark import loop_split


def read(run):
    return loop_split.ack_median_ms(run, "held")
