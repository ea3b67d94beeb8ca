"""How far the outbox batches a step's yields: messages the replica's process
sent over the frames they left in (``msgs_out`` over ``frames_out`` of
``rt:engine.decode.dispatch``)."""

from benchmark import loop_split


def read(run):
    return loop_split.ratio(run, ("msgs_out",), ("frames_out",))
