"""bench/spans.py (the program's serve.* spans and named scopes in a
profiler trace) on a synthetic event list, the readers of the accepted
metrics on the recorded chip trace, and the queue-wait reader."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, spans
from bench import trace_reduce as tr

RECORDED = (Path(tr.__file__).parent / "testdata" /
            "dfm_refine_trace.json.gz")

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6   # ns


def host(name, start_ms, end_ms, line="python"):
    return (HOST, line, name, start_ms * MS, (end_ms - start_ms) * MS, "")


def dev(line, name, start_ms, end_ms, scope=""):
    return (DEV, line, name, start_ms * MS, (end_ms - start_ms) * MS, scope)


def synthetic():
    """Two micro-batches (7, 8) through the loop, a draft on the worker,
    and an execution no dispatch span holds; 100 ms traced."""
    ops, mods = tr.OPS_LINE, tr.MODULES_LINE
    return [
        host(tr.WINDOW_SPAN, 0, 100), host(tr.LOOP_SPAN, 0, 100),
        host("serve.wait", 0, 10), host("serve.flush", 10, 12),
        host("serve.draft_wait#7", 12, 15), host("serve.refine#7", 15, 45),
        host("serve.dispatch#7", 17, 44), host("serve.complete#7", 45, 48),
        host("serve.wait", 48, 60),
        host("serve.draft#8", 50, 58, line="worker"),
        host("serve.draft_wait#8", 60, 62), host("serve.refine#8", 62, 92),
        host("serve.dispatch#8", 63, 91), host("serve.complete#8", 92, 95),
        dev(mods, "jit_refine(11)", 18, 43),
        dev(ops, "%while.1 = f32[4] while(...)", 18, 43),
        dev(ops, "fusion.1", 18, 30, "backbone"),
        dev(ops, "fusion.2", 30, 40, "sample_step"),
        dev(ops, "copy.3", 40, 43, ""),
        dev(mods, "jit_decode(3)", 52, 57), dev(ops, "fusion.9", 52, 57),
        dev(mods, "jit_refine(11)", 64, 94),
        dev(ops, "fusion.1", 64, 80, "backbone"),
        dev(ops, "fusion.2", 80, 94, "sample_step"),
        dev(mods, "jit_refine(11)", 96, 99),
        dev(ops, "fusion.2", 96, 99, "sample_step"),
    ]


def test_idle_is_split_by_the_innermost_loop_span():
    by = spans.idle_by_stage(synthetic())
    # idle: [0,18] [43,52] [57,64] [94,96] [99,100]
    want = {"serve.wait": 17, "serve.flush": 2, "serve.draft_wait": 5,
            "serve.refine": 4, "serve.dispatch": 3, "serve.complete": 4,
            "outside": 2}
    assert by == pytest.approx({k: v / 1e3 for k, v in want.items()})


def test_host_idle_share_leaves_out_waiting_and_the_caller():
    events = synthetic()
    # flush 2 + draft_wait 5 + refine 4 + dispatch 3 + complete 4 ms
    assert spans.host_idle_share(events) == pytest.approx(18.0)
    idle = 100 * (1 - tr.reduce([e[:5] for e in events])["busy_s"] / 0.1)
    assert idle == pytest.approx(37.0)
    assert spans.host_idle_share(events) <= idle


def test_sample_step_share_of_the_refine_programs_leaf_time():
    events = synthetic()
    # leaves of the three refine executions: 12 + 10 + 3 + 16 + 14 + 3
    # ms, 10 + 14 + 3 of them sample_step; the decode's op is not counted
    assert spans.sample_step_share(events) == pytest.approx(100 * 27 / 58)
    assert spans.scoped_time(events) == pytest.approx(
        {"backbone": 0.028, "sample_step": 0.027, "": 0.003})
    unknown = [e[:5] + (None,) if e[1] == tr.OPS_LINE else e for e in events]
    assert spans.sample_step_share(unknown) is None
    assert spans.sample_step_share(events, program="jit_missing") is None


def test_executions_against_their_dispatch_spans():
    got = spans.dispatch_containment(synthetic())
    assert got == {"inside": 1, "crossing": 1, "outside": 1,
                   "most_out_ms": pytest.approx(3.0)}


def test_operations_take_the_scope_of_their_micro_batchs_program():
    """fusion.2 is sample_step in micro-batch 7's program and backbone in
    8's (here wholly inside its dispatch span); the execution no dispatch
    span holds keeps its scope from the merged text (None: they differ)."""
    scopes = {(16, 8): {"fusion.1": "backbone", "fusion.2": "sample_step"},
              (32, 8): {"fusion.1": "backbone", "fusion.2": "backbone"}}
    events = [host("serve.dispatch#8", 63, 95) if e[2] == "serve.dispatch#8"
              else e[:5] + (None,) if e[1] == tr.OPS_LINE else e
              for e in synthetic()]
    got = spans.rescope(events, {7: (16, 8), 8: (32, 8)}, scopes)
    assert spans.refine_ops(got) == pytest.approx({
        ("fusion.1", "backbone"): 0.028, ("fusion.2", "sample_step"): 0.010,
        ("copy.3", None): 0.003, ("fusion.2", "backbone"): 0.014,
        ("fusion.2", None): 0.003})
    assert spans.sample_step_share(got) == pytest.approx(100 * 10 / 58)


def test_idle_gaps_take_the_narrowest_covering_span():
    gaps = spans.idle_gaps(synthetic())
    assert [n for n, _ in gaps] == ["serve.wait", "serve.complete",
                                    "serve.draft_wait", "serve.complete",
                                    tr.LOOP_SPAN]
    assert [round(s, 4) for _, s in gaps] == [0.018, 0.009, 0.007, 0.002,
                                             0.001]


def test_without_program_spans_the_readings_are_empty():
    events = [e for e in synthetic() if not e[2].startswith("serve.")]
    assert spans.host_idle_share(events) is None
    assert spans.dispatch_containment(events)["inside"] == 0
    assert spans.idle_by_stage(events) == pytest.approx({"outside": 0.037})


def test_scopes_from_program_text():
    path = "jit(refine)/while/body/closed_call/sample_step/reduce"
    assert spans.scope_of(path) == "sample_step"
    assert spans.scope_of("jit(refine)/while/body/backbone/dot") == "backbone"
    assert spans.scope_of("jit(refine)/while/body/add") == ""
    text = "\n".join([
        '  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, '
        'metadata={op_type="dot" op_name="jit(refine)/backbone/dot"}',
        '  ROOT %fusion.2 = s32[4]{0} fusion(%q), '
        'metadata={op_name="jit(refine)/sample_step/argmax"}',
        '  %copy.3 = f32[4]{0} copy(%r)',
    ])
    other = text.replace("backbone/dot", "sample_step/dot")
    assert spans.hlo_scopes([text]) == {"fusion.1": "backbone",
                                        "fusion.2": "sample_step"}
    assert spans.hlo_scopes([text, other])["fusion.1"] is None


def test_recorded_trace_reads_as_before():
    """The accepted readers of the recorded chip trace give the values
    they gave before the program had spans; the trace has no program
    span and no scope, so the span readings are empty."""
    events = tr.load(str(RECORDED))
    run = SimpleNamespace(trace=tr.reduce(events))
    assert harness.reader("device_idle_share").read(run) == pytest.approx(
        70.5326350311084)
    assert harness.reader("draft_device_share").read(run) == pytest.approx(
        1.3945589571401629)
    assert run.trace["idle_gaps"][0] == ["bench.draft_fn",
                                         pytest.approx(0.004633176)]
    six = [e + (None if e[1] == tr.OPS_LINE else "",) for e in events]
    assert spans.host_idle_share(six) is None
    assert spans.sample_step_share(six) is None
    assert spans.dispatch_containment(six)["outside"] == 2


def _run(batches):
    req = {"status": "completed", "done": 5.0, "seq_len": 8, "samples": 1}
    return SimpleNamespace(
        end=10.0, batches=batches,
        requests={0: dict(req, due=1.0, micro_batch=0),
                  1: dict(req, due=1.5, micro_batch=1),
                  2: dict(req, due=2.0, micro_batch=1),
                  3: dict(req, due=2.0, micro_batch=2, done=11.0)})


def test_queue_wait_reader():
    read = harness.reader("queue_wait_ms_p95").read
    run = _run([{"micro_batch": 0, "dispatch_s": 1.25},
                {"micro_batch": 1, "dispatch_s": 2.5},
                {"micro_batch": 2, "dispatch_s": 9.0}])
    # waits of the requests completed in the window: 250, 1000, 500 ms;
    # numpy's 95th percentile of three lies 0.9 of the way from 500 to 1000
    assert read(run) == pytest.approx(950.0)
    # a program without dispatch_s: no reading, and no error
    assert read(_run([{"micro_batch": 0}, {"micro_batch": 1}])) is None
