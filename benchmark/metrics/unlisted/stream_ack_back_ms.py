"""Median of what is left of a yield's ack after the outbox's and the
owner's parts (``ack_us - out_us - in_us - held_us`` of ``rt:stream.yield``):
the reply's way back and the REPLICA's loop before it resumed the stream."""

from benchmark import loop_split


def read(run):
    return loop_split.ack_median_ms(run, "back")
