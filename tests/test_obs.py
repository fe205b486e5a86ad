"""Observability-layer tests: span-tracer ring semantics (wrap-around,
eviction order, thread safety), Chrome trace-event export schema
(Perfetto-loadable ph/ts/dur/pid/tid, flow arrows), metrics registry
(keys, snapshots, deltas, histograms, concurrency), and the serving
integration contracts — the registry terminal ledger matches
``stream_report`` exactly (conservation), every request's flow chain
runs admission→terminal, and tracing never perturbs served tokens."""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from repro.obs import (
    DEFAULT_LATENCY_BUCKETS_S, Histogram, MetricsRegistry, NullTracer,
    PeriodicMetricsLogger, SpanTracer, load_trace, metric_key,
    parse_metric_key, stage_breakdown, to_trace_events, validate_trace,
    write_chrome_trace,
)


# ---------------------------------------------------------------------------
# tracer ring buffer
# ---------------------------------------------------------------------------

def test_ring_keeps_everything_under_capacity():
    tr = SpanTracer(capacity=8)
    for i in range(5):
        tr.instant(f"ev{i}", track="t")
    assert len(tr) == 5
    assert tr.emitted == 5 and tr.dropped == 0
    assert [r.name for r in tr.records()] == [f"ev{i}" for i in range(5)]


def test_ring_wrap_around_evicts_oldest_first():
    tr = SpanTracer(capacity=4)
    for i in range(10):
        tr.instant(f"ev{i}")
    assert len(tr) == 4
    assert tr.emitted == 10 and tr.dropped == 6
    # survivors are exactly the newest 4, still oldest-first
    assert [r.name for r in tr.records()] == ["ev6", "ev7", "ev8", "ev9"]


def test_ring_clear_resets_retained_but_not_totals():
    tr = SpanTracer(capacity=4)
    for i in range(6):
        tr.instant(f"ev{i}")
    tr.clear()
    assert len(tr) == 0 and tr.records() == []
    assert tr.emitted == 6 and tr.dropped == 2  # lifetime counters survive
    tr.instant("after")
    assert [r.name for r in tr.records()] == ["after"]


def test_ring_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        SpanTracer(capacity=0)


def test_concurrent_emit_is_exact():
    tr = SpanTracer(capacity=64)
    n_threads, per_thread = 8, 200

    def emit(tid):
        for i in range(per_thread):
            tr.instant(f"t{tid}.{i}", track=f"track{tid}")

    threads = [threading.Thread(target=emit, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.emitted == n_threads * per_thread
    assert len(tr) == 64
    assert tr.dropped == n_threads * per_thread - 64
    assert len(tr.records()) == 64


def test_span_records_duration_and_result_args():
    tr = SpanTracer()
    with tr.span("outer", track="work", fixed=1) as sp:
        with tr.span("inner", track="work"):
            time.sleep(0.01)
        sp["result"] = "hit"  # attached mid-span, must land in the record
    recs = {r.name: r for r in tr.records()}
    assert recs["inner"].ts >= recs["outer"].ts
    assert recs["outer"].dur >= recs["inner"].dur > 0
    assert recs["outer"].ph == "X"
    assert recs["outer"].args == {"fixed": 1, "result": "hit"}


def test_span_recorded_even_when_body_raises():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("doomed"):
            raise RuntimeError("boom")
    assert [r.name for r in tr.records()] == ["doomed"]


def test_null_tracer_is_inert_but_api_compatible():
    tr = NullTracer()
    tr.instant("x", track="t", flow_id=1, flow_ph="s", a=1)
    with tr.span("y", track="t", b=2) as sp:
        sp["cache"] = "hit"  # writable throwaway dict
    assert tr.enabled is False
    assert len(tr) == 0 and tr.records() == []
    assert tr.emitted == 0 and tr.dropped == 0


def _profiled(tmp_path, fn):
    """Run ``fn`` under a CPU ``jax.profiler`` trace; its result and the
    host plane's events as ``(line, name, start_ns, end_ns)``."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    return out, events


def test_profiler_sink_puts_spans_on_the_profiler_clock(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((32, 32))
    jax.block_until_ready(f(x))                     # compiled before
    tr = SpanTracer(profiler=True)

    def work():
        with tr.span("dispatch", track="refine_dispatch",
                     profile="serve.dispatch#3"):
            jax.block_until_ready(f(x))
        with tr.span("plain"):
            pass
        tr.instant("mark")

    _, events = _profiled(tmp_path, work)
    span, = [(s, e) for _, n, s, e in events if n == "serve.dispatch#3"]
    assert any(n == "plain" for _, n, _, _ in events)
    assert not any(n == "mark" for _, n, _, _ in events)  # ring only
    # the jitted call's own host events lie inside the span: one clock
    inner = [(s, e) for _, n, s, e in events
             if n.startswith("PjitFunction") or n == "dot_general"]
    assert inner and all(span[0] <= s and e <= span[1] for s, e in inner)
    # the ring records as it always did
    assert [r.name for r in tr.records()] == ["dispatch", "plain", "mark"]


def test_obs_imports_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "import repro.obs as o\n"
            "t = o.SpanTracer()\n"
            "with t.span('s', profile='serve.s'): pass\n"
            "assert [r.name for r in t.records()] == ['s']\n"
            "try:\n"
            "    o.SpanTracer(profiler=True)\n"
            "except ImportError:\n"
            "    print('ok')\n")
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"


# ---------------------------------------------------------------------------
# Chrome trace-event export
# ---------------------------------------------------------------------------

def _sample_tracer():
    tr = SpanTracer()
    tr.instant("request_admitted", track="admission", flow_id=7,
               flow_ph="s", request_id=7, priority="standard")
    with tr.span("draft", track="draft_worker", bucket=16):
        pass
    tr.instant("request_packed", track="flush", flow_id=7, flow_ph="t",
               request_id=7)
    with tr.span("refine", track="refine_dispatch", bucket=16) as sp:
        sp["cache"] = "hit"
    tr.instant("request_terminal", track="terminal", flow_id=7,
               flow_ph="f", request_id=7, status="completed")
    return tr


def test_export_schema_is_valid_trace_event_json(tmp_path):
    tr = _sample_tracer()
    path = tmp_path / "trace.json"
    doc = write_chrome_trace(str(path), tr, metadata={"mode": "test"})
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"] == {"mode": "test"}
    assert load_trace(str(path)) == doc  # plain-JSON round trip

    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert names == {"admission", "draft_worker", "refine_dispatch",
                     "flush", "terminal"}
    # pipeline-ordered tids: admission row above the terminal row
    tid_of = {e["args"]["name"]: e["tid"] for e in meta}
    assert tid_of["admission"] < tid_of["draft_worker"] < tid_of["terminal"]

    for e in events:
        assert "pid" in e and "tid" in e
        if e["ph"] != "M":
            assert "ts" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"

    flows = [e for e in events if e["ph"] in ("s", "t", "f")]
    assert [f["ph"] for f in flows] == ["s", "t", "f"]
    assert all(f["id"] == 7 and f["name"] == "request" for f in flows)
    assert flows[-1]["bp"] == "e"  # finish binds to its enclosing slice

    assert validate_trace(doc, expected_requests=1) == []


def test_unknown_track_gets_its_own_tid():
    tr = SpanTracer()
    tr.instant("tick", track="custom_stage")
    events = to_trace_events(tr.records())
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["args"]["name"] for e in meta} == {"custom_stage"}


def test_stage_breakdown_aggregates_per_track_and_span():
    tr = SpanTracer()
    for _ in range(3):
        with tr.span("draft", track="draft_worker"):
            pass
    with tr.span("refine", track="refine_dispatch"):
        time.sleep(0.01)
    rows = stage_breakdown(to_trace_events(tr.records()))
    by_name = {r["name"]: r for r in rows}
    assert by_name["draft"]["count"] == 3
    assert by_name["refine"]["count"] == 1
    assert rows[0]["name"] == "refine"  # sorted by total time desc
    for r in rows:
        assert r["max_ms"] >= r["mean_ms"] > 0


def test_validate_trace_catches_broken_schema_and_chains():
    assert validate_trace({}) == ["traceEvents missing or not a list"]

    base = {"pid": 1, "tid": 1, "ts": 0.0}
    bad_x = {"ph": "X", "name": "spanless", **base}          # no dur
    orphan_s = {"ph": "s", "name": "request", "id": 3, **base}
    admitted_only = {"ph": "i", "name": "request_admitted", "s": "t",
                     "args": {"request_id": 9}, **base}
    problems = validate_trace(
        {"traceEvents": [bad_x, orphan_s, admitted_only]})
    assert any("missing dur" in p for p in problems)
    assert any("start without finish" in p for p in problems)
    assert any("admitted but no terminal" in p for p in problems)

    ok = to_trace_events(_sample_tracer().records())
    assert validate_trace({"traceEvents": ok}) == []
    assert any("chains 1 != expected requests 2" in p
               for p in validate_trace({"traceEvents": ok},
                                       expected_requests=2))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metric_key_round_trip_and_label_sorting():
    key = metric_key("serve.terminal", {"status": "shed", "priority": "p"})
    assert key == "serve.terminal{priority=p,status=shed}"
    assert parse_metric_key(key) == (
        "serve.terminal", {"priority": "p", "status": "shed"})
    assert parse_metric_key("plain") == ("plain", {})
    assert metric_key("plain", {}) == "plain"


def test_registry_get_or_create_identity():
    reg = MetricsRegistry()
    assert reg.counter("a", x=1) is reg.counter("a", x=1)
    assert reg.counter("a", x=1) is not reg.counter("a", x=2)
    assert reg.gauge("g") is reg.gauge("g")
    assert reg.histogram("h") is reg.histogram("h")


def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("g")
    g.set(2.5)
    g.add(-0.5)
    assert g.value == 2.0
    h = reg.histogram("h", buckets=(1.0, 2.0))
    for v in (0.5, 1.0, 1.5, 99.0):   # edge-inclusive + overflow
        h.observe(v)
    snap = h.snapshot()
    assert snap["counts"] == [2, 1, 1]
    assert snap["count"] == 4 and snap["sum"] == pytest.approx(102.0)
    with pytest.raises(ValueError):
        Histogram(buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram(buckets=())


def test_snapshot_deltas_and_label_matched_sums():
    reg = MetricsRegistry()
    reg.counter("serve.terminal", status="completed", priority="std").inc(3)
    reg.counter("serve.terminal", status="shed", priority="be").inc(1)
    reg.counter("untouched").inc(0)
    m0 = reg.snapshot()
    reg.counter("serve.terminal", status="completed", priority="std").inc(2)
    reg.counter("serve.terminal", status="timed_out", priority="std").inc(1)

    deltas = reg.counter_deltas(m0)
    assert deltas == {
        "serve.terminal{priority=std,status=completed}": 2,
        "serve.terminal{priority=std,status=timed_out}": 1,
    }  # zero deltas filtered out
    assert reg.sum_counters("serve.terminal", m0) == 3
    assert reg.sum_counters("serve.terminal", m0, status="completed") == 2
    assert reg.sum_counters("serve.terminal", None, status="shed") == 1
    assert reg.sum_counters("missing", m0) == 0


def test_registry_concurrent_increment_is_exact():
    reg = MetricsRegistry()
    n_threads, per_thread = 8, 500

    def work():
        for _ in range(per_thread):
            reg.counter("hot", shard="s").inc()
            reg.histogram("lat").observe(0.01)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hot", shard="s").value == n_threads * per_thread
    assert reg.histogram("lat").count == n_threads * per_thread


def test_dump_json_round_trips_the_snapshot(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c", k="v").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)

    path = tmp_path / "metrics.json"
    reg.dump_json(str(path))
    loaded = json.loads(path.read_text())
    assert loaded == reg.snapshot()
    assert loaded["counters"]["c{k=v}"] == 2
    assert loaded["gauges"]["g"] == 1.5
    assert loaded["histograms"]["h"]["count"] == 1


def test_periodic_logger_emits_delta_lines():
    reg = MetricsRegistry()
    reg.counter("warm").inc(5)          # pre-start state must not re-print
    lines = []
    logger = PeriodicMetricsLogger(reg, interval_s=0.02, sink=lines.append)
    logger.start()
    reg.counter("serve.admitted").inc(3)
    time.sleep(0.08)
    logger.stop(final_tick=True)
    assert lines and all(l.startswith("[metrics t=") for l in lines)
    joined = "\n".join(lines)
    assert "serve.admitted=3" in joined
    assert "warm" not in joined
    with pytest.raises(ValueError):
        PeriodicMetricsLogger(reg, interval_s=0.0)


# ---------------------------------------------------------------------------
# serving integration: registry == ledger, chains cover every request
# ---------------------------------------------------------------------------

class ToyFlow:
    """Constant peaked logits — the refine converges to one mode."""

    def __init__(self, vocab=11, mode=2):
        self.vocab = vocab
        self.mode = mode

    def dfm_apply(self, params, x, t, extras=None):
        import jax.numpy as jnp

        return jnp.zeros(x.shape + (self.vocab,)).at[..., self.mode].set(30.0)


def _make_scheduler(**kw):
    from repro.serving import WarmStartScheduler, uniform_draft

    return WarmStartScheduler(
        flow_model=ToyFlow(), flow_params={},
        draft_fn=uniform_draft(11), cold_nfe=20, default_t0=0.8, **kw)


def _mixed_requests():
    from repro.serving import ServeRequest

    return [ServeRequest(request_id=i, seq_len=L, num_samples=n,
                         seed=100 + i, t0=t0)
            for i, (L, n, t0) in enumerate(
                [(5, 2, None), (12, 3, None), (8, 1, 0.5), (30, 4, None)])]


def test_stream_report_terminals_equal_registry_counters():
    sched = _make_scheduler(max_rows=8)
    m0 = sched.metrics.snapshot()
    list(sched.serve_stream(_mixed_requests()))
    rep = sched.stream_report

    # the conservation contract: every terminal-status counter in the
    # registry equals the stream report's ledger, status by status
    for status, n in rep["terminal"].items():
        assert sched.metrics.sum_counters(
            "serve.terminal", m0, status=status) == n, status
    assert rep["conservation"]["balanced"]
    assert sched.metrics.sum_counters("serve.admitted", m0) \
        == rep["num_requests"]
    flushes = {reason: sched.metrics.sum_counters("serve.flush", m0,
                                                  reason=reason)
               for reason in rep["flush_reasons"]}
    assert flushes == rep["flush_reasons"]


def test_trace_chains_cover_every_ledger_request(tmp_path):
    from repro.serving import AdmissionQueue, QueueFull, ServeRequest

    tracer = SpanTracer()
    sched = _make_scheduler(max_rows=8, tracer=tracer)
    queue = AdmissionQueue(max_depth=2, metrics=sched.metrics)
    # 2 best_effort fill the bounded queue; 2 premium arrivals shed them
    for i, cls in enumerate(["best_effort", "best_effort",
                             "premium", "premium"]):
        try:
            queue.push(ServeRequest(request_id=i, seq_len=8, num_samples=1,
                                    seed=50 + i, priority=cls))
        except QueueFull:
            pass
    queue.close()
    list(sched.serve_stream(source=queue))
    rep = sched.stream_report
    assert rep["terminal"]["completed"] == 2
    assert rep["terminal"]["shed"] == 2
    assert rep["conservation"]["balanced"]

    doc = write_chrome_trace(str(tmp_path / "t.json"), tracer)
    # acceptance criterion: admission→terminal chains cover 100% of the
    # requests in the conservation ledger (completed AND shed)
    n_ledger = sum(rep["terminal"].values())
    assert validate_trace(doc, expected_requests=n_ledger) == []
    statuses = sorted(e["args"]["status"] for e in doc["traceEvents"]
                      if e.get("name") == "request_terminal")
    assert statuses == ["completed", "completed", "shed", "shed"]


def test_tracing_does_not_perturb_served_tokens(tmp_path):
    import numpy as np

    base = {c.request_id: c for c in
            _make_scheduler(max_rows=8).serve_stream(_mixed_requests())}
    tracer = SpanTracer()
    traced_sched = _make_scheduler(max_rows=8, tracer=tracer)
    traced = {c.request_id: c for c in
              traced_sched.serve_stream(_mixed_requests())}
    # the profiler sink, with a profiler session running (the same
    # scheduler: its programs are compiled already)
    traced_sched.tracer = SpanTracer(profiler=True)
    profiled, _ = _profiled(tmp_path, lambda: {
        c.request_id: c for c in
        traced_sched.serve_stream(_mixed_requests())})
    for run in (traced, profiled):
        assert set(run) == set(base)
        for rid in base:
            np.testing.assert_array_equal(run[rid].tokens, base[rid].tokens)
            assert run[rid].nfe == base[rid].nfe
    assert tracer.emitted > 0  # the traced run really did record spans
    tracks = {r.track for r in tracer.records()}
    assert {"admission", "draft_worker", "refine_dispatch",
            "flush", "terminal"} <= tracks


@pytest.mark.parametrize("fused_block", [1, 2])
def test_stream_spans_carry_their_micro_batch_id(tmp_path, fused_block):
    """Every micro-batch's spans on the profiler's host plane carry the
    ``#k`` its requests receive as ``CompletedRequest.micro_batch``, the
    worker's draft span included; the ring's spans carry it as an arg.
    The fused path's step blocks leave the id alone."""
    tracer = SpanTracer(profiler=True)
    sched = _make_scheduler(max_rows=8, tracer=tracer,
                            fused_block=fused_block)
    out, events = _profiled(tmp_path,
                            lambda: list(sched.serve_stream(_mixed_requests())))
    ks = {c.micro_batch for c in out}
    assert ks == {b["micro_batch"] for b in sched.stream_report["batches"]}
    assert len(ks) > 1
    for stage in ("draft", "draft_wait", "refine", "dispatch", "complete"):
        got = [int(n.split("#")[1]) for _, n, _, _ in events
               if n.startswith(f"serve.{stage}#")]
        assert sorted(got) == sorted(ks), stage
    assert any(n == "serve.flush" for _, n, _, _ in events)
    for r in tracer.records():
        if r.name in ("draft", "refine", "dispatch", "complete"):
            assert r.args["micro_batch"] in ks


def test_queue_wait_is_arrival_to_refine_dispatch():
    """``serve.queue_wait_s`` observes, per packed request, the stream
    clock's start of its micro-batch's refine dispatch less its arrival;
    each batch entry carries its flush, dispatch and completion times."""
    from repro.serving import ServeRequest

    class TickingClock:
        """Advances 0.25 s at every reading, so each stage has its time."""

        def __init__(self):
            self.t = 10.0

        def time(self):
            self.t += 0.25
            return self.t

        def sleep(self, dt):
            self.t += dt

    sched = _make_scheduler(max_rows=8)
    arrivals = {0: 9.0, 1: 9.5, 2: 9.75, 3: 8.0}
    reqs = [ServeRequest(request_id=r.request_id, seq_len=r.seq_len,
                         num_samples=r.num_samples, seed=r.seed, t0=r.t0,
                         arrival_s=arrivals[r.request_id])
            for r in _mixed_requests()]
    out = {c.request_id: c for c in
           sched.serve_stream(reqs, clock=TickingClock())}
    batches = {b["micro_batch"]: b for b in sched.stream_report["batches"]}
    for b in batches.values():
        assert b["flushed_s"] < b["dispatch_s"] < b["done_s"]
        assert "queue_wait_s" not in b
    waits = [batches[c.micro_batch]["dispatch_s"] - arrivals[rid]
             for rid, c in out.items()]
    h = sched.metrics.snapshot()["histograms"]["serve.queue_wait_s"]
    assert h["count"] == len(waits)
    assert h["sum"] == pytest.approx(sum(waits))


def test_distilled_tier_spans_and_counters_in_registry():
    """Distilled micro-batches record tier-labelled `distill` spans and
    their gate/fallback counters in the registry, and the stream report's
    distilled section equals the registry deltas."""
    import jax
    import numpy as np

    from repro.drafting import (
        AdaptiveT0Policy, DistilledRefiner, T0Calibration,
    )
    from repro.serving import DISTILLED, DISTILLED_TIER, ServeRequest

    def scorer(toks):
        import jax.numpy as jnp
        return jnp.asarray(toks, jnp.float32).mean(axis=-1) / 10.0

    policy = AdaptiveT0Policy(
        scorer=scorer,
        calibration=T0Calibration(scores=(0.1, 0.9), t0s=(0.5, 0.9),
                                  t0_floor=0.5, t0_ceil=0.9),
        bin_width=0.1)
    model = DistilledRefiner(vocab_size=11)
    tracer = SpanTracer()
    sched = _make_scheduler(
        t0_policy=policy, tracer=tracer, distilled_model=model,
        distilled_params=model.init(jax.random.key(0)),
        distilled_accept_score=-100.0)
    m0 = sched.metrics.snapshot()
    reqs = [ServeRequest(request_id=i, seq_len=8, num_samples=2, seed=i,
                         tier=DISTILLED_TIER if i % 2 else "guaranteed")
            for i in range(4)]
    out = {c.request_id: c for c in sched.serve_stream(reqs)}
    rep = sched.stream_report

    assert out[1].status == out[3].status == DISTILLED
    assert rep["distilled"]["served"] == 2 == sched.metrics.sum_counters(
        "serve.terminal", m0, status=DISTILLED)
    assert rep["distilled"]["gate_evals"] == sched.metrics.sum_counters(
        "distilled.gate_evals", m0) > 0
    # the distill stage records its own tier-labelled span, separate
    # from the guaranteed refine span
    names = {(r.name, r.args.get("tier")) for r in tracer.records()
             if r.name in ("refine", "distill")}
    assert ("distill", DISTILLED_TIER) in names
    assert ("refine", "guaranteed") in names
    # distilled compile keys are tier-suffixed in the per-key cache view
    per_key = [parse_metric_key(k)[1]
               for k in sched.metrics.counter_deltas(m0)
               if k.startswith("jit_cache.per_key")]
    assert any(DISTILLED_TIER in lbl.get("key", "") for lbl in per_key)
    np.testing.assert_array_equal(  # tracing really served tokens
        out[1].tokens.shape, (2, 8))


def test_admission_queue_ledger_lives_in_registry():
    from repro.serving import AdmissionQueue, QueueFull, ServeRequest

    reg = MetricsRegistry()
    q1 = AdmissionQueue(max_depth=1, metrics=reg)
    q2 = AdmissionQueue(metrics=reg)        # same registry, distinct ledger
    q1.push(ServeRequest(request_id=0, seq_len=8, num_samples=1, seed=1))
    with pytest.raises(QueueFull):
        q1.push(ServeRequest(request_id=1, seq_len=8, num_samples=1, seed=2))
    q2.push(ServeRequest(request_id=2, seq_len=8, num_samples=1, seed=3))
    s1, s2 = q1.stats(), q2.stats()
    assert (s1["offered"], s1["accepted"], s1["rejected"]) == (2, 1, 1)
    assert (s2["offered"], s2["accepted"], s2["rejected"]) == (1, 1, 0)
    # both ledgers visible in the shared registry under distinct labels
    assert reg.sum_counters("admission.offered") == 3


def test_cost_model_reports_into_registry():
    from repro.serving import PerNFECostModel

    reg = MetricsRegistry()
    cm = PerNFECostModel(metrics=reg)
    cm.observe((16, 4, 7), 2.1, 7, compiled=True)   # jit-cache miss
    cm.observe((16, 4, 7), 0.07, 7)                 # steady state
    assert reg.counter("cost_model.observations").value == 2
    assert reg.gauge("cost_model.compile_s").value > 0
    assert reg.gauge("cost_model.per_nfe_s").value == pytest.approx(
        cm.per_nfe_s())
