"""Median time from a ``stream_yield``'s frame leaving the replica to the
owner's handler running (``in_us`` of ``rt:stream.yield``): the wire and the
OWNER's loop, the ingress's where requests come over HTTP.  An ingress that
falls behind shows here."""

from benchmark import loop_split


def read(run):
    return loop_split.ack_median_ms(run, "in")
