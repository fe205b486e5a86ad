"""Share (%) of the traced window the device spent in the draft stage's
programs: the LSTM engine's prefill and decode and the per-row key
derivation (names as the device trace gives them)."""

from bench import readings

PROGRAMS = ("jit_decode", "jit_prefill_batched", "jit_prefill_scan",
            "jit__derive_row_keys")


def read(run):
    return readings.program_share(run, PROGRAMS)
