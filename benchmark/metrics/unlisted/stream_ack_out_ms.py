"""Median time a ``stream_yield`` waited in the REPLICA's outbox before its
frame was packed (``out_us`` of ``rt:stream.yield``): the tick's other
streams resume first, so at 64 live streams this is half a step's fan-out."""

from benchmark import loop_split


def read(run):
    return loop_split.ack_median_ms(run, "out")
