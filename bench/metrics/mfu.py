"""Model FLOP/s utilization (%): useful model FLOPs of the requests
completed in the window over window x the chip's bf16 peak. Useful means
each completed row's refine steps, one evaluation each at the request's
own length: padding rows and slots are left out."""

from bench import readings


def read(run):
    if run.peaks is None:
        return None
    useful = sum(r["samples"] * r["nfe"]
                 * run.flops.forward_flops(run.model, 1, r["seq_len"])
                 for r in readings.completed_in_window(run))
    if useful <= 0:
        return None
    return 100.0 * useful / (run.seconds * run.peaks["bf16_flops_per_s"])
