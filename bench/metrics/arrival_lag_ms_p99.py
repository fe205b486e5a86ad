"""99th percentile of how late the load generator sent each request
against its due time (open loop only): a starved generator must not read
as a fast server. Requests due once the profiler is on are left out: it
takes host time of its own."""

import numpy as np


def read(run):
    if run.mix["loop"] != "open" or not run.requests:
        return None
    lag = [(r["sent"] - r["due"]) * 1e3 for r in run.requests.values()
           if r["due"] < run.untraced_until]
    if not lag:
        return None
    return float(np.percentile(np.asarray(lag), 99))
