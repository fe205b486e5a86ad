"""95th percentile of the time to result in a cell above capacity, over
every request sent in the window: recorded, not judged (just above
capacity the queue sets it)."""

from bench import readings


def read(run):
    return readings.ttr_ms(run, 95)
