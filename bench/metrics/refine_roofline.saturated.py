"""Share (%) of its roofline the refine scan reached: for each refine
program execution in the trace, steps x max(FLOPs / bf16 peak, bytes /
HBM bandwidth) of one evaluation at the padded shape (bench/flops.py),
summed, over the device time of those executions."""

from bench import readings


def read(run):
    return readings.refine_roofline(run, "jit_refine")
