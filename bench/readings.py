"""Computations shared by the metric readers (``bench/metrics``), on the
record of one run (``harness.run_cell``)."""

from __future__ import annotations

import numpy as np


def completed_in_window(run):
    return [r for r in run.requests.values()
            if r["status"] == "completed" and r["done"] <= run.end]


def ttr_ms(run, q: float):
    """``q``-th percentile of the time to result of every request sent in
    the window, from its due time to the moment the client received its
    result; a request that was not served counts as never served."""
    ttr = [(r["done"] - r["due"]) * 1e3
           if r["status"] == "completed" else float("inf")
           for r in run.requests.values()]
    if not ttr:
        return None
    return float(np.percentile(np.asarray(ttr), q))


def window_batches(run):
    """Micro-batches whose results reached the client inside the window."""
    return [b for b in run.batches
            if run.origin <= run.mb_done.get(b["micro_batch"], -1) <= run.end]


def program_share(run, programs):
    """Share (%) of the traced window spent in the named device programs;
    None when the trace holds none of them."""
    if run.trace is None:
        return None
    t = sum(s for name, s in run.trace["program_s"].items()
            if name in programs)
    if t <= 0:
        return None
    return 100.0 * t / run.trace["window_s"]


# how much longer than its device execution the scheduler's host-timed
# refine dispatch (``flow_time_s``) may take: dispatch, the guarantee
# checks and the wake-up, 5-7 ms on a v5e
HOST_SLACK_S, HOST_SLACK_SHARE = 0.03, 0.1


def refine_roofline(run, program: str = "jit_refine"):
    """Least time of the refine programs traced (steps x the roofline of
    one evaluation at the padded shape) over their device time, in %.

    A pair of execution and micro-batch counts only where the device time
    agrees with the scheduler's own host timing of that micro-batch's
    refine: where the host and device clocks drift apart, a short
    execution can fall between another micro-batch's end and its marker,
    and would be charged that micro-batch's work."""
    if run.trace is None or run.peaks is None:
        return None
    from bench import trace_reduce

    batches = {b["micro_batch"]: b for b in run.batches}
    least = spent = 0.0
    for k, secs in trace_reduce.match_executions(run.trace, program,
                                                 "bench.mb_done"):
        b = batches.get(k)
        if b is None:
            continue
        over = b["flow_time_s"] - secs
        if not -0.002 <= over <= max(HOST_SLACK_S, HOST_SLACK_SHARE * secs):
            continue
        least += b["nfe"] * run.flops.roofline_s(
            run.model, b["padded_rows"], b["bucket_len"], run.peaks)
        spent += secs
    if spent <= 0:
        return None
    return 100.0 * least / spent
