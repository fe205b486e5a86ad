"""Generated tokens (length x samples) of the requests completed inside
the window, over the window."""

from bench import readings


def read(run):
    done = readings.completed_in_window(run)
    if not done:
        return None
    return sum(r["seq_len"] * r["samples"] for r in done) / run.seconds
