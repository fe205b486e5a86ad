"""Seconds from the start of the process to the first due request:
weights, every program the traffic opens (compiled or loaded from the
cache), the draft engine and the flush policy's cost model."""


def read(run):
    return run.setup_s
