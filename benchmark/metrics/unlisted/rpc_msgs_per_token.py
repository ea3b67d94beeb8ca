"""Requests, notifies and replies the replica's process sent and received a
streamed token: ``msgs_out + msgs_in`` over ``yields`` of
``rt:engine.decode.dispatch``."""

from benchmark import loop_split


def read(run):
    return loop_split.ratio(run, ("msgs_out", "msgs_in"), ("yields",))
