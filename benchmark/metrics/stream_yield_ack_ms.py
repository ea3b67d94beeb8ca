"""Median time from a ``stream_yield`` request's send to its ack, the
``ack_us`` of ``rt:stream.yield``: what the transport adds to a token."""

from benchmark import host_regions


def read(run):
    return host_regions.median_ms(run, "stream.yield", "ack_us")
