"""Share (%) of the traced window with no operation running on the
device: 1 - busy (union of operation intervals) / window."""


def read(run):
    if run.trace is None or not run.trace["devices"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
