"""bench/trace_reduce.py on a small synthesised trace, and on a recorded
excerpt of a chip trace kept with the benchmark."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import flops, readings
from bench import trace_reduce as tr

RECORDED = (Path(tr.__file__).parent / "testdata" /
            "dfm_refine_trace.json.gz")

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6   # ns


def ev(plane, line, name, start_ms, dur_ms):
    return (plane, line, name, start_ms * MS, dur_ms * MS)


def synthetic():
    return [
        ev(HOST, "python", tr.WINDOW_SPAN, 0, 100),
        ev(HOST, "python", tr.LOOP_SPAN, 0, 100),
        ev(HOST, "worker", "bench.draft_fn", 66, 8),
        ev(HOST, "python", "bench.mb_done#7", 41, 0.1),
        ev(HOST, "python", "bench.mb_done#8", 91, 0.1),
        # two refine executions and a draft, as programs
        ev(DEV, tr.MODULES_LINE, "jit_refine(12)", 10, 30),
        ev(DEV, tr.MODULES_LINE, "jit_decode(3)", 60, 10),
        ev(DEV, tr.MODULES_LINE, "jit_refine(14)", 75, 15),
        # ops: overlapping inside the first refine, one crossing the
        # window's end
        ev(DEV, tr.OPS_LINE, "%while.1 = f32[4] while(...)", 10, 30),
        ev(DEV, tr.OPS_LINE, "%fusion.2 = f32[4] fusion(...)", 12, 5),
        ev(DEV, tr.OPS_LINE, "%fusion.3 = f32[4] fusion(...)", 20, 25),
        ev(DEV, tr.OPS_LINE, "%dot.4 = f32[4] dot(...)", 60, 10),
        ev(DEV, tr.OPS_LINE, "%fusion.2 = f32[4] fusion(...)", 75, 15),
        ev(DEV, tr.OPS_LINE, "%copy.9 = f32[4] copy(...)", 95, 20),
    ]


def test_busy_is_the_union_of_overlapping_ops_inside_the_window():
    s = tr.reduce(synthetic())
    # [10, 45] + [60, 70] + [75, 90] + [95, 100] (clipped at the end)
    assert s["busy_s"] == pytest.approx((35 + 10 + 15 + 5) / 1e3)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["devices"] == 1
    idle = 1 - s["busy_s"] / s["window_s"]
    assert idle == pytest.approx(0.35)


def test_device_time_by_program_and_op_name():
    s = tr.reduce(synthetic())
    assert s["program_s"] == pytest.approx(
        {"jit_refine": 0.045, "jit_decode": 0.010})
    ops = dict(s["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.020)
    assert ops["copy.9"] == pytest.approx(0.005)      # clipped
    # the loop's own event spans its body's operations: leaves only
    assert "while.1" not in ops and list(ops)[0] == "fusion.3"


def test_idle_gaps_are_named_by_the_narrowest_covering_span():
    gaps = tr.reduce(synthetic())["idle_gaps"]
    # longest first: [0,10], [45,60], [70,75], [90,95]
    assert [round(g, 4) for _, g in gaps] == [0.015, 0.01, 0.005, 0.005]
    named = {round(g, 4): w for w, g in gaps}
    assert named[0.015] == tr.LOOP_SPAN
    assert gaps[2][0] == "bench.draft_fn"        # [70, 75] under the draft


def test_refine_executions_pair_with_their_micro_batch():
    s = tr.reduce(synthetic())
    got = tr.match_executions(s, "jit_refine", "bench.mb_done")
    assert got == [(7, pytest.approx(0.030)), (8, pytest.approx(0.015))]


def test_roofline_counts_only_pairs_the_host_timing_confirms():
    """Micro-batch 8's host-timed refine took 60 ms, not the 15 ms of the
    execution paired with it: that pair is another micro-batch's and is
    left out; micro-batch 7's 32 ms confirm its 30 ms execution."""
    s = tr.reduce(synthetic())
    model = {"architecture": "dfm-dit",
             "hidden_size": 64, "intermediate_size": 128, "vocab_size": 32,
             "num_hidden_layers": 2, "num_attention_heads": 4,
             "num_key_value_heads": 4, "head_dim": 16, "time_embed_dim": 32,
             "use_bias": False, "tie_word_embeddings": False,
             "activation_dtype": "float32", "weight_dtype": "float32"}
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    batch = {"bucket_len": 16, "padded_rows": 8, "nfe": 7}
    run = SimpleNamespace(
        trace=s, peaks=peaks, model=model, flops=flops,
        batches=[dict(batch, micro_batch=7, flow_time_s=0.032),
                 dict(batch, micro_batch=8, flow_time_s=0.060)])
    least = 7 * flops.roofline_s(model, 8, 16, peaks)
    assert readings.refine_roofline(run) == pytest.approx(100 * least / 0.030)
    run.batches[0]["flow_time_s"] = 0.1
    assert readings.refine_roofline(run) is None


def test_a_trace_without_its_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.trace_window"):
        tr.reduce([e for e in synthetic() if e[2] != tr.WINDOW_SPAN])


def test_names():
    assert tr.program_name("jit_refine(1234)") == "jit_refine"
    assert tr.op_name("%fusion.253 = f32[4,12] fusion(x)") == "fusion.253"
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [[0, 3], [5, 7]]


def test_recorded_chip_trace():
    """31 ms of a DFM-DiT run on a TPU v5e: two micro-batches, each a draft
    decode and a refine scan, with the benchmark's host spans."""
    events = tr.load(str(RECORDED))
    s = tr.reduce(events)
    ops = [(st, st + d) for p, l, n, st, d in events
           if p == "/device:TPU:0" and l == tr.OPS_LINE]
    # the union by brute force over 1-microsecond cells
    cells = set()
    for lo, hi in ops:
        cells.update(range(int(lo // 1000), int(-(-hi // 1000))))
    assert s["busy_s"] == pytest.approx(len(cells) / 1e6, rel=0.02)
    assert 0.2 < s["busy_s"] / s["window_s"] < 0.4
    refine = [d for p, l, n, st, d in events
              if l == tr.MODULES_LINE and n.startswith("jit_refine(")]
    assert len(refine) == 2
    assert s["program_s"]["jit_refine"] == pytest.approx(sum(refine) / 1e9)
    assert set(s["program_s"]) >= {"jit_refine", "jit_decode",
                                   "jit__derive_row_keys"}
    got = tr.match_executions(s, "jit_refine", "bench.mb_done")
    assert [k for k, _ in got] == [44, 45]
    assert [t for _, t in got] == pytest.approx([r / 1e9 for r in refine])
    assert not any(n.startswith("while") for n, _ in s["device_ops"])
    assert s["idle_gaps"][0][0] == "bench.draft_fn"
