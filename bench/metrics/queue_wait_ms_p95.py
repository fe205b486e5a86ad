"""95th percentile, over the requests completed in the window, of the
time from a request's due time to the start of its micro-batch's refine
dispatch (``dispatch_s`` of ``stream_report["batches"]``, on the
scheduler's monotonic clock, the clock of the due times). None where the
program does not report ``dispatch_s``."""

import numpy as np

from bench import readings


def read(run):
    dispatch = {b["micro_batch"]: b["dispatch_s"] for b in run.batches
                if "dispatch_s" in b}
    waits = [(dispatch[r["micro_batch"]] - r["due"]) * 1e3
             for r in readings.completed_in_window(run)
             if r["micro_batch"] in dispatch]
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits), 95))
