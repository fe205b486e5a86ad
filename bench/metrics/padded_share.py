"""Share (%) of the refined token slots that were padding: 1 - real
tokens / (padded rows x bucket length), over the window's micro-batches
(stream_report batches; real tokens from the requests each one served)."""

from collections import defaultdict

from bench import readings


def read(run):
    real = defaultdict(int)
    for r in run.requests.values():
        if r["status"] == "completed":
            real[r["micro_batch"]] += r["seq_len"] * r["samples"]
    batches = readings.window_batches(run)
    slots = sum(b["padded_rows"] * b["bucket_len"] for b in batches)
    if not slots:
        return None
    used = sum(real[b["micro_batch"]] for b in batches)
    return 100.0 * (1.0 - used / slots)
