"""Median time to result over every request due in the window, drained
after it closes: from the due time to the client's receipt."""

from bench import readings


def read(run):
    return readings.ttr_ms(run, 50)
