"""The test-only size override of the benchmark: each cell at a tiny
model, through the same code path as on the chip (see
``bench/harness.run_cell``)."""

import copy
import time

from bench import harness

MODEL = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "head_dim": 16, "intermediate_size": 128, "time_embed_dim": 32}
SIZES = {
    "dfm-dit.text8-poisson": {
        "config": {"model": dict(MODEL, num_key_value_heads=4),
                   "serving": {"max_rows": 8}},
        "mix": {"buckets": {"min": 16, "max": 32},
                "length": {"min": 9, "max": 32}, "rate_rps": 12.0,
                "trace_seconds": 0.5},
        "limits": {"sample_rows": 6},
    },
    "starcoder2-3b.code-saturated": {
        "config": {"model": dict(MODEL, num_key_value_heads=2,
                                 vocab_size=512),
                   "serving": {"max_rows": 16}},
        "mix": {"buckets": {"min": 16, "max": 32},
                "length": {"min": 9, "max": 32}, "clients": 4, "block": 16,
                "trace_seconds": 0.5},
        "limits": {"sample_rows": 6},
    },
}
CELLS = sorted(SIZES)


def longer(cell):
    """The tiny cell with blocks of 65-128 tokens: long enough for a
    bfloat16 draft's recurrent state to drift from float32."""
    size = copy.deepcopy(SIZES[cell])
    size["mix"].update(buckets={"min": 128, "max": 128},
                       length={"min": 65, "max": 128})
    size["limits"] = {"sample_rows": 24}
    return size


def run(cell, seed=123456789012, seconds=1.5, trace=False, control="",
        size=None):
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.monotonic(),
                            size=size or SIZES[cell], control=control,
                            log=lambda *a, **k: None)
