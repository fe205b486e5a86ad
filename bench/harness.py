"""One run of one benchmark cell: set-up, the measured window, the trace,
the metrics and the correctness check.

Everything specific to a cell is data found by name from
``BENCHMARK.json``: the configuration file (``bench/configs``), the
traffic mix (``bench/traffic``), the limits of the check
(``bench/limits/<config>.json``) and one reader per metric
(``bench/metrics/<metric>.py``), and the configuration's architecture
(``bench/archs/<architecture>.py``: its weights, its ``ModelConfig`` and
its reference). This module builds the served stack of a configuration
of any architecture there, drives it with a mix, and hands the record of
the run to the readers.

The window drives ``WarmStartScheduler.serve_stream`` from an
``AdmissionQueue``. Open loop: a generator thread sends each request at
its due time. Closed loop: each client sends its next request when its
last result comes back. Every request is timed on the host's monotonic
clock from its due time to the moment the client receives its result.
"""

from __future__ import annotations

import copy
import gc
import glob
import importlib.util
import itertools
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = (_merge(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def cell_spec(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell's entry and everything it names."""
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return SimpleNamespace(
        cell=cell, config=_json(root / conf["file"]),
        mix=_json(root / "bench" / "traffic" / f"{cell['traffic']}.json"),
        limits=_json(root / "bench" / "limits" / f"{cell['config']}.json"),
        end_to_end=e2e, per_layer=per_layer)


def reader(name: str):
    """The metric's reader module ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def buckets(mix: dict):
    b, out = mix["buckets"]["min"], []
    while b <= mix["buckets"]["max"]:
        out.append(b)
        b *= 2
    return out


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else ``.jax_cache/`` at the root of the checkout (a fixed path: the
    path is part of the cache's key). Every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class DraftRecorder:
    """The scheduler's ``draft_fn``: the engine's, plus a host copy of
    each draft (taken before the refine loop donates the buffer) and its
    row keys, so that the check can start the reference from exactly the
    drafts the timed path refined."""

    def __init__(self, fn):
        self.fn = fn
        self.calls = []

    def __call__(self, keys, seq_len):
        import jax

        with jax.profiler.TraceAnnotation("bench.draft_fn"):
            x = self.fn(keys, seq_len)
            self.calls.append((keys, np.asarray(x)))
        return x

    def rows(self) -> dict:
        """Row key data -> that row's draft."""
        import jax

        out = {}
        for keys, x in self.calls:
            kd = np.asarray(jax.random.key_data(keys))
            for b in range(x.shape[0]):
                out[tuple(kd[b].tolist())] = x[b]
        return out


class CompileCounter:
    """Host times of the backend compiles (``jax.monitoring``)."""

    def __init__(self):
        self.times = []

    def _on(self, event, duration, **kwargs):
        if event == COMPILE_EVENT:
            self.times.append(time.monotonic())

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def build(spec: SimpleNamespace, seed: int, program_dtype: str = ""):
    """Backbone, LSTM draft engine and scheduler, with the benchmark's
    weights from ``seed`` (made on the device in one jitted call each, in
    the configuration's dtype). ``program_dtype`` runs the backbone in
    another dtype, on the same weights cast to it."""
    import jax
    from repro.drafting import ARDraftEngine, LSTMDraftAdapter
    from repro.models import LSTMConfig, LSTMModel, build_model
    from repro.serving import WarmStartScheduler

    from bench import archs, weights

    cfg, mix = spec.config, spec.mix
    m, dr, s = cfg["model"], cfg["draft"], cfg["serving"]
    arch = archs.load(m["architecture"])
    w = weights.make(seed, arch.shapes(m), m["weight_dtype"], 0)
    if program_dtype:
        m = dict(m, weight_dtype=program_dtype, activation_dtype=program_dtype)
    model = build_model(arch.model_config(cfg["name"], m))
    params = arch.to_program(
        weights.cast(w, program_dtype) if program_dtype else w, model,
        jax.random.key(0))
    lstm = LSTMModel(LSTMConfig(vocab_size=m["vocab_size"], hidden=dr["hidden"],
                                num_layers=dr["num_layers"],
                                embed_dim=dr["embed_dim"]))
    wl = weights.make(seed, weights.lstm_shapes(dr, m["vocab_size"]),
                      "float32", 1)
    lparams = weights.to_program_lstm(wl, lstm, jax.random.key(0))
    engine = ARDraftEngine(LSTMDraftAdapter(model=lstm), lparams,
                           max_len=mix["buckets"]["max"],
                           temperature=dr["temperature"], bos=dr["bos"])
    recorder = DraftRecorder(engine.as_draft_fn())
    sched = WarmStartScheduler(
        flow_model=model, flow_params=params, draft_fn=recorder,
        cold_nfe=s["cold_nfe"], default_t0=s["t0"],
        temperature=s["temperature"], max_rows=s["max_rows"],
        min_bucket=mix["buckets"]["min"], max_bucket=mix["buckets"]["max"],
        row_quantum=s["row_quantum"])
    return sched, recorder, w, wl


def warm(sched, spec: SimpleNamespace) -> None:
    """Serve every (bucket, padded rows) micro-batch the mix can open:
    once to compile (or load) each refine and draft program, and, where
    the flush policy reads the per-NFE cost model (``slo_ms``), once more
    so that each key has a steady-state cost estimate."""
    from repro.serving import ServeRequest

    s, mix = spec.config["serving"], spec.mix
    most = max(mix["samples"]["values"])
    ids = itertools.count()
    passes = 2 if mix.get("slo_ms") else 1
    for _ in range(passes):
        for rows in range(s["row_quantum"], s["max_rows"] + 1,
                          s["row_quantum"]):
            reqs = []
            for b in buckets(mix):
                left = rows
                while left > 0:
                    k = min(left, most)
                    i = next(ids)
                    reqs.append(ServeRequest(request_id=i, seq_len=b,
                                             num_samples=k, seed=i))
                    left -= k
            for _ in sched.serve_stream(reqs):
                pass


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class Tracer:
    """Starts the profiler at ``start`` and stops it ``seconds`` later
    (host monotonic times), from a thread of its own; the traced window
    is marked by the host span ``bench.trace_window``."""

    def __init__(self, start: float, seconds: float):
        self.start, self.seconds = start, seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.error = None
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        import jax

        try:
            time.sleep(max(0.0, self.start - time.monotonic()))
            jax.profiler.start_trace(self.dir)
            with jax.profiler.TraceAnnotation("bench.trace_window"):
                time.sleep(self.seconds)
            jax.profiler.stop_trace()
        except Exception as err:  # noqa: BLE001 — reported by the caller
            self.error = err

    def events(self):
        from bench import trace_reduce

        self.thread.join()
        if self.error is not None:
            raise RuntimeError(f"profiler failed: {self.error}")
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        return trace_reduce.extract(paths[0])

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def serve_window(sched, spec: SimpleNamespace, seed: int, seconds: float,
                 tracer_at=None) -> SimpleNamespace:
    """Serve the mix for ``seconds`` and drain what is due; returns the
    record of every request sent in the window."""
    import jax
    from repro.serving import AdmissionQueue, ServeRequest

    from bench import loadgen

    mix = spec.mix
    reqs = loadgen.schedule(mix, seed, seconds)
    queue = AdmissionQueue()
    rec = {}
    lock = threading.Lock()
    mb_done = {}
    origin = time.monotonic() + 0.01
    end = origin + seconds
    tracer = None
    if tracer_at is not None:
        tracer = Tracer(origin + tracer_at[0], tracer_at[1])
        tracer.thread.start()

    def send(r, due):
        with jax.profiler.TraceAnnotation("bench.submit"):
            with lock:
                rec[r.index] = {"seq_len": r.seq_len,
                                "samples": r.num_samples, "seed": r.seed,
                                "due": due, "sent": time.monotonic(),
                                "done": None, "status": None}
            queue.push(ServeRequest(request_id=r.index, seq_len=r.seq_len,
                                    num_samples=r.num_samples, seed=r.seed,
                                    arrival_s=due))

    producer = None
    pool = iter(reqs)
    closing = False
    if mix["loop"] == "open":
        def produce():
            loadgen.open_loop(reqs, origin, send)
            queue.close()

        producer = threading.Thread(target=produce, daemon=True)
        producer.start()
    else:
        time.sleep(max(0.0, origin - time.monotonic()))
        for _ in range(mix["clients"]):
            send(next(pool), time.monotonic())

    stream = sched.serve_stream(source=queue, slo_ms=mix.get("slo_ms"),
                                idle_timeout_s=mix["idle_timeout_s"])
    with CompileCounter() as compiles:
        while True:
            with jax.profiler.TraceAnnotation("bench.serve_stream"):
                c = next(stream, None)
            if c is None:
                break
            now = time.monotonic()
            with jax.profiler.TraceAnnotation("bench.client"):
                if c.micro_batch not in mb_done and c.micro_batch >= 0:
                    mb_done[c.micro_batch] = now
                    with jax.profiler.TraceAnnotation(
                            f"bench.mb_done#{c.micro_batch}"):
                        pass
                with lock:
                    r = rec[c.request_id]
                r.update(done=now, status=c.status, nfe=c.nfe, t0=c.t0,
                         bucket_len=c.bucket_len, micro_batch=c.micro_batch,
                         tokens=np.asarray(c.tokens))
                if mix["loop"] == "closed" and not closing:
                    if now < end:
                        r_next = next(pool, None)
                        if r_next is not None:
                            send(r_next, now)
                    else:
                        closing = True
                        queue.close()
    if producer is not None:
        producer.join()
    return SimpleNamespace(
        origin=origin, end=end, seconds=seconds, requests=rec,
        batches=(sched.stream_report or {}).get("batches", []),
        mb_done=mb_done, compile_times=compiles.times, tracer=tracer)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks or [0]))}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, size: dict = None, control: str = "",
             log=print) -> dict:
    """Set up, serve, measure and check one run of ``workload``.

    ``size`` is a test-only override merged into the cell's files (a
    tiny model on the CPU through this same path). ``control`` replaces
    the program by the cell's control for the check (see
    ``bench/correctness.py``); the benchmark's own runs never set it.
    """
    import jax

    from bench import correctness, flops, readings, trace_reduce

    spec = cell_spec(workload)
    if size:
        for part in ("config", "mix", "limits"):
            setattr(spec, part, _merge(getattr(spec, part), size.get(part, {})))
    program_dtype = next((c.split(":", 1)[1] for c in control.split("+")
                          if c.startswith("program:")), "")
    enable_compile_cache()
    chips = spec.cell["chips"]

    sched, recorder, w, wl = build(spec, seed, program_dtype)
    warm(sched, spec)
    recorder.calls.clear()
    gc.collect()
    tracer_at = None
    if trace:
        # near the end of the window, so that the host time the profiler
        # takes to stop and write falls mostly into the drain
        length = min(spec.mix["trace_seconds"], seconds / 2)
        tracer_at = (max(0.0, seconds - 2.0 - length), length)
    win = serve_window(sched, spec, seed, seconds, tracer_at)
    setup_s = win.origin - t_start
    device = device_info(chips)
    drafts = recorder.rows()

    summary = None
    if trace:
        try:
            summary = trace_reduce.reduce(win.tracer.events())
        finally:
            win.tracer.close()
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]

    kind = device["kind"]
    run = SimpleNamespace(
        seconds=seconds, setup_s=setup_s, origin=win.origin, end=win.end,
        untraced_until=win.origin + (tracer_at[0] if trace else seconds),
        requests=win.requests, batches=win.batches, mb_done=win.mb_done,
        compiles_in_window=sum(win.origin <= t <= win.end
                               for t in win.compile_times),
        trace=summary, model=spec.config["model"],
        serving=spec.config["serving"], mix=spec.mix,
        peaks=(flops.peaks(kind) if device["platform"] == "tpu" else None),
        flops=flops)
    wanted = spec.per_layer if trace else spec.end_to_end
    metrics = {}
    for m in wanted:
        value = reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    log(f"info micro_batches_in_window {len(readings.window_batches(run))}",
        file=sys.stderr)

    # free the program's state before the reference takes the chip
    del sched, recorder
    gc.collect()
    checks = correctness.check(run, drafts, w, wl, spec, seed,
                               control=control,
                               log=lambda line: log(line, file=sys.stderr))
    for name, (value, limit) in checks.items():
        log(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sent = win.requests.values()
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": len(sent),
        "failed": sum(r["status"] != "completed" for r in sent),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, (v, lim) in checks.items()}
    return result
