"""Serving launcher: warm-start generation demo/driver.

``python -m repro.launch.serve --t0 0.8 --num 8`` trains a tiny draft LSTM
+ DFM denoiser on the synthetic corpus (or restores a checkpoint produced
by train.py) and serves a batch of requests through the WarmStartServer,
printing the guarantee report.

Drafting subsystem modes (see ``src/repro/drafting/``):
  --draft ar-kv   serve drafts through the KV-cached row-keyed
                  ``ARDraftEngine`` (pack-invariant, cross-micro-batch
                  cache reuse) instead of the batch-keyed LSTM adapter;
  --t0 auto       per-request adaptive t0: drafts are quality-scored
                  under the learned path and each request enters the
                  refine at its calibrated (binned) warm-start time.
                  Implies --scheduler.
  --t0 bandit     contextual-bandit t0: per-(bucket, score-bin) arms
                  over the calibrated t0 grid, learning online from the
                  verify-step probe reward minus measured refine cost.
                  Implies --scheduler.
  --speculative   draft-and-verify fast path: requests whose every row
                  clears the acceptance probe ship their drafts with 0
                  refine NFE (ACCEPTED_DRAFT); rejected requests re-pack
                  bit-identically to speculation-off serving. Implies
                  --scheduler (needs --t0 auto/bandit; auto is enabled
                  when neither was requested).

Distilled SLO tier (implies --scheduler and an adaptive --t0 policy):
  --tier distilled          serve the request set as the cheap
                            ``tier="distilled"`` class: a few-step
                            self-distilled refiner head (trained on
                            (draft, refined, t0) pairs harvested from a
                            guaranteed warm-up pass, or restored from
                            --distill-ckpt) serves each request at
                            NFE = K in {1, 2} behind a probe-score
                            quality floor; requests that miss the floor
                            fall back to the guaranteed path
                            bit-identical to a fresh guaranteed request;
  --distill-ckpt DIR        restore the distilled head from DIR if a
                            checkpoint exists there, else train one and
                            save it to DIR;
  --distilled-nfe K         steps for the distilled head (1 or 2);
  --distilled-accept-score  explicit quality floor; default: two-pass
                            calibration (pass 1 serves with the floor
                            open and takes the median split of the
                            per-request min probe scores, pass 2 is the
                            real serve);
  --check-distilled         exit non-zero unless the distilled tier
                            really served (served > 0), the quality
                            floor really rejected (fallbacks > 0), the
                            ledger conserves every admission, and the
                            distilled NFE is <= 2.

Streaming / SLO admission modes (imply --scheduler):
  --stream           serve through the streaming admission loop
                     (``serve_stream``): results print as each
                     micro-batch finishes, not at end-of-run;
  --slo-ms MS        per-request latency SLO — partial buckets flush
                     when a request's deadline budget (minus the
                     measured per-NFE refine-cost estimate) runs out;
  --arrival-rate R   Poisson open-loop arrival replay at R requests/s
                     (0 = admit the whole set up front);
  --queue-depth N    bound the admission queue at N requests — overflow
                     sheds lowest-priority-first or rejects (QueueFull),
                     every outcome ledgered in the stream report;
  --timeout-ms MS    per-request latency budget: requests that exceed it
                     surface as TIMED_OUT (never silently dropped);
  --priority CLASS   priority class (premium | standard | best_effort)
                     for the streamed requests — shedding never touches
                     a higher class before a lower one.

Telemetry (imply --scheduler; see ``src/repro/obs/``):
  --trace-out F.json        record pipeline spans (draft worker, refine
                            dispatch, scoring pre-pass, flush decisions)
                            and per-request admission→terminal flow
                            arrows; writes Chrome trace-event JSON that
                            loads in https://ui.perfetto.dev. Summarise
                            offline with ``tools/trace_summary.py``;
  --metrics-out F.json      dump the metrics registry (counters, gauges,
                            histograms) at end of run;
  --metrics-interval-s S    print live counter-delta lines every S
                            seconds while streaming.
"""

from __future__ import annotations

import argparse
import threading

import jax
import numpy as np

from repro.configs.base import RunConfig
from repro.configs.dfm_dit import tiny_config
from repro.core import CorruptionDraft, KNNRefinementCoupling, WarmStartPath, pair_iterator
from repro.data import SyntheticCorpus, TEXT_VOCAB, decode
from repro.launch.compile_cache import enable_compile_cache
from repro.models import LSTMConfig, LSTMModel, build_model
from repro.optim import AdamW
from repro.serving import WarmStartScheduler, WarmStartServer, batch_keyed_draft
from repro.training import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--t0", default="0.8",
                    help="warm-start time in [0,1), 'auto' for per-request "
                         "quality-adaptive t0, or 'bandit' for the online "
                         "contextual-bandit policy")
    ap.add_argument("--speculative", action="store_true",
                    help="speculative draft-and-verify: accept requests "
                         "whose every row's probe score clears the "
                         "acceptance threshold with ZERO refine steps; "
                         "rejected requests serve bit-identically to "
                         "speculation-off mode (implies --scheduler and "
                         "an adaptive --t0 policy)")
    ap.add_argument("--accept-score", type=float, default=None,
                    help="speculative acceptance threshold on the probe "
                         "score (default: the calibration's top anchor)")
    ap.add_argument("--tier", choices=("guaranteed", "distilled"),
                    default="guaranteed",
                    help="request class to serve: 'distilled' routes the "
                         "set through the few-step distilled refiner tier "
                         "behind its quality floor (implies --scheduler "
                         "and an adaptive --t0 policy)")
    ap.add_argument("--distill-ckpt", default=None, metavar="DIR",
                    help="distilled-head checkpoint dir: restore from it "
                         "when present, else train on harvested pairs and "
                         "save to it")
    ap.add_argument("--distilled-nfe", type=int, default=1,
                    help="distilled refiner steps K (1 or 2)")
    ap.add_argument("--distilled-accept-score", type=float, default=None,
                    help="probe-score quality floor for the distilled "
                         "tier (default: two-pass median-split "
                         "calibration over the request set)")
    ap.add_argument("--check-distilled", action="store_true",
                    help="gate mode: exit non-zero unless the distilled "
                         "tier served > 0, fell back > 0, conserved every "
                         "admission, and shipped at NFE <= 2")
    ap.add_argument("--per-row-t0", action="store_true",
                    help="per-ROW adaptive t0: rows of one request enter "
                         "the shared refine scan at their own calibrated "
                         "step instead of the request-min t0")
    ap.add_argument("--cold-nfe", type=int, default=32)
    ap.add_argument("--num", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fused-step", action="store_true",
                    help="use the streamed Pallas ws_step kernel for the "
                         "per-step sampling (auto-selects TPU/interpret)")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve a mixed-size request stream through the "
                         "continuous-batching WarmStartScheduler instead of "
                         "the one-shot WarmStartServer")
    ap.add_argument("--draft", choices=("lstm", "ar-kv"), default="lstm",
                    help="draft stage: 'lstm' = batch-keyed LSTM.generate "
                         "adapter (demo), 'ar-kv' = row-keyed KV-cached "
                         "ARDraftEngine (pack-invariant serving)")
    ap.add_argument("--stream", action="store_true",
                    help="stream results through the SLO-aware admission "
                         "loop (serve_stream) instead of end-of-run batch "
                         "serving; implies --scheduler")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-request latency SLO in ms (streaming mode): "
                         "partial buckets flush when a deadline would blow")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival replay rate in requests/s for "
                         "--stream (0 = admit everything up front)")
    ap.add_argument("--queue-depth", type=int, default=0,
                    help="bound the streaming admission queue at this many "
                         "requests: overflow sheds the lowest priority "
                         "class (or rejects) instead of queueing unboundedly "
                         "(0 = unbounded)")
    ap.add_argument("--timeout-ms", type=float, default=0.0,
                    help="per-request latency budget in ms for --stream: an "
                         "expired request resolves TIMED_OUT instead of "
                         "being served late (0 = no timeout)")
    ap.add_argument("--priority", choices=("premium", "standard",
                                           "best_effort"),
                    default="standard",
                    help="priority class for the streamed requests: premium "
                         "is shed last and dispatched first, best_effort "
                         "is shed first and carries no SLO deadline")
    ap.add_argument("--trace-out", default=None, metavar="trace.json",
                    help="record pipeline spans + per-request flow arrows "
                         "and write a Chrome trace-event JSON here (load "
                         "it in https://ui.perfetto.dev); implies "
                         "--scheduler")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="span ring-buffer capacity for --trace-out "
                         "(oldest records evict beyond it)")
    ap.add_argument("--metrics-out", default=None, metavar="metrics.json",
                    help="dump the metrics registry snapshot (counters / "
                         "gauges / histograms) to this JSON file at the "
                         "end of the run; implies --scheduler")
    ap.add_argument("--metrics-interval-s", type=float, default=0.0,
                    help="print a live '[metrics t=..]' counter-delta line "
                         "every this many seconds while serving "
                         "(0 = off; streaming mode)")
    args = ap.parse_args()
    enable_compile_cache()

    if (args.trace_out or args.metrics_out) and not args.scheduler:
        print("--trace-out/--metrics-out imply --scheduler; enabling it")
        args.scheduler = True

    if args.check_distilled and args.tier != "distilled":
        print("--check-distilled implies --tier distilled; enabling it")
        args.tier = "distilled"
    t0_mode = str(args.t0).lower()
    if args.speculative and t0_mode not in ("auto", "bandit"):
        print("--speculative needs an adaptive t0 policy; enabling --t0 auto")
        t0_mode = "auto"
    if args.tier == "distilled" and t0_mode not in ("auto", "bandit"):
        print("--tier distilled needs an adaptive t0 policy "
              "(the quality floor scores under it); enabling --t0 auto")
        t0_mode = "auto"
    t0_auto = t0_mode in ("auto", "bandit")
    if (t0_auto or args.stream) and not args.scheduler:
        print(f"--{f't0 {t0_mode}' if t0_auto else 'stream'} implies "
              "--scheduler; enabling it")
        args.scheduler = True
    # adaptive serving may go as shallow as the calibration floor (the
    # worst tier's target t0); train the flow path there so every served
    # t >= t0_train is in-distribution. Fixed-t0 serving trains at the
    # served t0.
    if t0_auto:
        from repro.drafting.quality import DEFAULT_TIERS
        t0_train = min(t0 for _, t0 in DEFAULT_TIERS)
    else:
        t0_train = float(args.t0)

    cfg = tiny_config(vocab_size=TEXT_VOCAB, seq_len=args.seq_len)
    model = build_model(cfg)
    corpus = SyntheticCorpus(seed=args.seed)
    data = corpus.sequences(2048, args.seq_len, seed=1)
    rng = np.random.default_rng(args.seed)

    # draft LSTM (the paper's §4.2 draft role)
    lstm_cfg = LSTMConfig(vocab_size=TEXT_VOCAB, hidden=128, num_layers=1, embed_dim=64)
    lstm = LSTMModel(lstm_cfg)
    lparams = lstm.init(jax.random.key(7))
    lopt = AdamW(learning_rate=1e-2)
    lstate = lopt.init(lparams)
    lgrad = jax.jit(jax.value_and_grad(lstm.loss))
    for i in range(args.train_steps):
        idx = rng.integers(0, data.shape[0], size=16)
        loss, g = lgrad(lparams, data[idx])
        lparams, lstate = lopt.update(g, lstate, lparams)
    print(f"draft LSTM trained, final loss={float(loss):.3f}")

    # WS-DFM pairs: LSTM drafts refined by kNN into the corpus
    drafts = np.asarray(lstm.generate(lparams, jax.random.key(3), 512, args.seq_len))
    coupling = KNNRefinementCoupling(k=2, k_inject=2, max_candidates=2048)
    src, tgt = coupling.build(data, drafts, rng)
    run = RunConfig(total_steps=args.train_steps, batch_size=32, t0=t0_train,
                    learning_rate=1e-3, log_every=50)
    trainer = Trainer(model, cfg, run, path=WarmStartPath(t0=t0_train))
    state = trainer.init_state(jax.random.key(0))
    state = trainer.fit(state, pair_iterator(src, tgt, 32, rng),
                        log_fn=lambda i, m: print(f"  flow step {i}: {m['ce']:.3f}"))

    if args.scheduler:
        # largest pow2 bucket the flow model's positions cover; min_bucket
        # must not exceed it or every submit would overflow the bucket cap
        max_bucket = 1 << (args.seq_len.bit_length() - 1)
        if args.draft == "ar-kv":
            from repro.drafting import ARDraftEngine, LSTMDraftAdapter

            engine = ARDraftEngine(LSTMDraftAdapter(model=lstm), lparams,
                                   max_len=max_bucket)
            draft_fn = engine.as_draft_fn()
            print("draft stage: KV-cached row-keyed ARDraftEngine "
                  "(pack-invariant, cross-micro-batch cache reuse)")
        else:
            engine = None
            draft_fn = batch_keyed_draft(
                lambda key, num, L: lstm.generate(lparams, key, num, L))
            print("note: LSTM draft is batch-keyed (batch_keyed_draft) — "
                  "outputs are reproducible for a fixed packing but not "
                  "invariant to micro-batch composition; use --draft ar-kv "
                  "for request-seeded serving")
        t0_policy = None
        if t0_auto:
            from repro.drafting import (
                AdaptiveT0Policy, BanditT0Policy, fit_t0_calibration,
                make_quality_scorer,
            )

            scorer = make_quality_scorer(model.dfm_apply, state.params)
            calib = fit_t0_calibration(scorer, data[:, :max_bucket],
                                       TEXT_VOCAB, seed=args.seed)
            if t0_mode == "bandit":
                t0_policy = BanditT0Policy(scorer=scorer, calibration=calib,
                                           seed=args.seed)
                print("t0 policy: contextual bandit over the calibrated "
                      "grid (online verify-step reward)")
            else:
                t0_policy = AdaptiveT0Policy(scorer=scorer, calibration=calib)
            print(f"adaptive t0 calibration: scores {calib.scores} -> "
                  f"t0 {calib.t0s}")
        tracer = None
        if args.trace_out:
            from repro.obs import SpanTracer
            tracer = SpanTracer(capacity=args.trace_capacity)
        rng_sizes = np.random.default_rng(args.seed + 1)
        sizes = [int(rng_sizes.integers(max_bucket // 2, max_bucket + 1))
                 for _ in range(args.num)]
        sched_kw = dict(
            flow_model=model, flow_params=state.params,
            draft_fn=draft_fn,
            cold_nfe=args.cold_nfe,
            default_t0=t0_train if t0_auto else float(args.t0),
            min_bucket=min(8, max_bucket), max_bucket=max_bucket,
            t0_policy=t0_policy,
            per_row_t0=args.per_row_t0,
            speculative=args.speculative,
            accept_score=args.accept_score,
        )
        distilled_kw = {}
        if args.tier == "distilled":
            from repro.drafting import (
                DistilledRefiner, PairBuffer, distilled_checkpoint_exists,
                restore_distilled, save_distilled, train_distilled,
            )

            # full-bucket requests: the gate scores the packed bucket
            # rows, so serving at seq_len == bucket makes the two-pass
            # calibration score exactly what the serving gate scores
            sizes = [max_bucket] * args.num
            dmodel = DistilledRefiner(vocab_size=TEXT_VOCAB)
            if args.distill_ckpt and distilled_checkpoint_exists(
                    args.distill_ckpt):
                dparams = restore_distilled(args.distill_ckpt, dmodel)
                print(f"distilled head restored from {args.distill_ckpt}")
            else:
                # harvest (draft, refined, t0) pairs from a guaranteed
                # warm-up pass over the same request set
                buf = PairBuffer()
                harvest = WarmStartScheduler(**sched_kw, pair_buffer=buf)
                for i, L in enumerate(sizes):
                    harvest.submit(seq_len=L, num_samples=1, seed=100 + i,
                                   t0=None)
                harvest.run()
                dparams, drep = train_distilled(
                    dmodel, buf, key=jax.random.key(13), epochs=8)
                print(f"distilled head trained on {drep.pairs} harvested "
                      f"pairs: loss {drep.first_loss:.3f} -> "
                      f"{drep.final_loss:.3f}, "
                      f"agreement {drep.final_agreement:.2f}")
                if args.distill_ckpt:
                    save_distilled(args.distill_ckpt, dparams,
                                   step=drep.steps)
                    print(f"distilled head saved to {args.distill_ckpt}")
            gate = args.distilled_accept_score
            if gate is None:
                # two-pass gate calibration, pass 1: serve the set with
                # the floor wide open and median-split the per-request
                # min probe scores (same seeds + packing as the real
                # pass, so pass-1 outputs are bit-identical to pass 2)
                probe = WarmStartScheduler(
                    **sched_kw, distilled_model=dmodel,
                    distilled_params=dparams,
                    distilled_nfe=args.distilled_nfe,
                    distilled_accept_score=-1e9)
                prids = [probe.submit(seq_len=L, num_samples=1,
                                      seed=100 + i, t0=None,
                                      tier="distilled")
                         for i, L in enumerate(sizes)]
                pres, _ = probe.run()
                mins = sorted(
                    float(np.asarray(t0_policy.scorer(
                        pres[rid].tokens)).min()) for rid in prids)
                if mins[0] == mins[-1]:
                    gate = mins[0]
                    print("warning: every request scored "
                          f"{gate:.3f} under the distilled head; the "
                          "quality floor cannot split this set")
                else:
                    mid = len(mins) // 2
                    gate = (mins[mid - 1] + mins[mid]) / 2.0
                print(f"distilled quality floor calibrated: "
                      f"score >= {gate:.3f} "
                      f"(min scores {mins[0]:.3f}..{mins[-1]:.3f})")
            distilled_kw = dict(
                distilled_model=dmodel, distilled_params=dparams,
                distilled_nfe=args.distilled_nfe,
                distilled_accept_score=gate)
        sched = WarmStartScheduler(**sched_kw, tracer=tracer, **distilled_kw)

        def check_distilled(rep, *, stream):
            """--check-distilled gate: the tier must have really served,
            really fallen back, conserved every admission, and shipped
            at NFE <= 2."""
            d = rep.get("distilled") or {}
            fails = []
            if not d.get("enabled"):
                fails.append("distilled tier not enabled")
            if d.get("served", 0) <= 0:
                fails.append("distilled served 0 requests")
            if d.get("fallbacks", 0) <= 0:
                fails.append("quality floor never fell back")
            if d.get("nfe", 99) > 2:
                fails.append(f"distilled NFE {d.get('nfe')} > 2")
            if stream:
                if not rep["conservation"]["balanced"]:
                    fails.append("conservation ledger unbalanced")
                if rep["terminal"]["distilled"] != d.get("served"):
                    fails.append("terminal ledger != distilled served")
            else:
                if d.get("served", 0) + d.get("fallbacks", 0) \
                        != d.get("requests", -1):
                    fails.append("served + fallbacks != distilled requests")
            status = "FAILED" if fails else "OK"
            print(f"check-distilled: {status}"
                  + ("".join(f"\n  - {f}" for f in fails)))
            if fails:
                raise SystemExit(1)

        def write_telemetry():
            """Flush trace / metrics artifacts at the end of a run."""
            if args.trace_out:
                from repro.obs import stage_breakdown, write_chrome_trace
                trace = write_chrome_trace(
                    args.trace_out, tracer,
                    metadata={"mode": "stream" if args.stream else "batch",
                              "t0": t0_mode, "num": args.num})
                print(f"\ntrace: {len(trace['traceEvents'])} events -> "
                      f"{args.trace_out} (dropped {tracer.dropped} spans; "
                      f"open in ui.perfetto.dev)")
                rows = stage_breakdown(trace)
                if rows:
                    print("per-stage time breakdown:")
                    for r in rows:
                        print(f"  {r['track']:>15s}/{r['name']:<16s} "
                              f"n={r['count']:<4d} total={r['total_ms']:8.1f}ms "
                              f"mean={r['mean_ms']:6.1f}ms "
                              f"max={r['max_ms']:6.1f}ms")
            if args.metrics_out:
                sched.metrics.dump_json(args.metrics_out)
                print(f"metrics: registry snapshot -> {args.metrics_out}")
        if args.speculative:
            print(f"speculative accept threshold: "
                  f"score >= {sched.accept_score:.3f}")

        if args.stream:
            from repro.serving import (
                ACCEPTED_DRAFT, COMPLETED, DISTILLED, AdmissionQueue,
                QueueFull,
            )

            queue = AdmissionQueue(
                max_depth=args.queue_depth or None, metrics=sched.metrics)
            mlogger = None
            if args.metrics_interval_s > 0:
                from repro.obs import PeriodicMetricsLogger
                mlogger = PeriodicMetricsLogger(
                    sched.metrics, interval_s=args.metrics_interval_s)
                mlogger.start()
            timeout_s = (args.timeout_ms / 1e3) if args.timeout_ms else None
            rng_arr = np.random.default_rng(args.seed + 2)

            def replay():
                for i, L in enumerate(sizes):
                    if args.arrival_rate > 0:
                        import time as _time
                        _time.sleep(float(
                            rng_arr.exponential(1.0 / args.arrival_rate)))
                    try:
                        queue.submit(seq_len=L, num_samples=1, seed=100 + i,
                                     t0=None,  # None -> policy / default
                                     priority=args.priority,
                                     timeout_s=timeout_s,
                                     tier=args.tier)
                    except QueueFull:
                        pass            # counted in the admission ledger
                queue.close()

            producer = threading.Thread(target=replay, daemon=True)
            producer.start()
            print(f"\nstreaming {args.num} requests "
                  f"(arrival rate {args.arrival_rate or 'inf'} req/s, "
                  f"SLO {args.slo_ms or '-'} ms, "
                  f"class {args.priority}, "
                  f"queue depth {args.queue_depth or 'unbounded'}, "
                  f"timeout {args.timeout_ms or '-'} ms):")
            for res in sched.serve_stream(source=queue, slo_ms=args.slo_ms,
                                          idle_timeout_s=0.02):
                if res.status == ACCEPTED_DRAFT:
                    print(f"  [{res.request_id}] ACCEPTED_DRAFT nfe=0 "
                          f"latency={res.latency_s * 1e3:.0f}ms  "
                          f"{decode(np.asarray(res.tokens[0]))}")
                    continue
                if res.status == DISTILLED:
                    print(f"  [{res.request_id}] DISTILLED nfe={res.nfe} "
                          f"latency={res.latency_s * 1e3:.0f}ms  "
                          f"{decode(np.asarray(res.tokens[0]))}")
                    continue
                if res.status != COMPLETED:
                    print(f"  [{res.request_id}] {res.status.upper()} "
                          f"({res.priority}, "
                          f"latency {res.latency_s * 1e3:.0f}ms)")
                    continue
                slo = ("" if res.slo_met is None
                       else f" slo={'OK' if res.slo_met else 'MISS'}")
                print(f"  [{res.request_id}] t0={res.t0:.2f} nfe={res.nfe} "
                      f"bucket={res.bucket_len} mb={res.micro_batch} "
                      f"flush={res.flush_reason} "
                      f"latency={res.latency_s * 1e3:.0f}ms{slo}  "
                      f"{decode(np.asarray(res.tokens[0]))}")
            producer.join()
            if mlogger is not None:
                mlogger.stop()
            rep = sched.stream_report
            lat = rep["latency_s"]
            att = rep["slo_attainment"]
            print(f"\nstream: "
                  f"{rep['completed'] + rep['accepted_draft'] + rep['distilled_served']} "
                  f"results ({rep['accepted_draft']} accepted drafts, "
                  f"{rep['distilled_served']} distilled) in "
                  f"{rep['num_micro_batches']} micro-batches, "
                  f"first result at {rep['time_to_first_result_s']:.3f}s, "
                  f"latency p50/p95/p99 = {lat['p50'] * 1e3:.0f}/"
                  f"{lat['p95'] * 1e3:.0f}/{lat['p99'] * 1e3:.0f} ms, "
                  f"SLO attainment "
                  f"{'-' if att is None else f'{att:.0%}'}, "
                  f"flushes {rep['flush_reasons']}")
            if rep.get("speculative"):
                spec = rep["speculative"]
                print(f"speculative: {spec['accepted']}/{spec['eligible']} "
                      f"accepted (rate {spec['accept_rate']:.0%}, "
                      f"threshold {spec['accept_score']:.3f})")
            if rep.get("bandit"):
                print(f"bandit arms: {len(rep['bandit'])} contexts learned")
            if (rep.get("distilled") or {}).get("enabled"):
                d = rep["distilled"]
                print(f"distilled: {d['served']} served at NFE={d['nfe']} "
                      f"({d['fallbacks']} quality-floor fallbacks, "
                      f"floor {d['gate_score']:.3f})")
            term = rep["terminal"]
            if any(v for k, v in term.items()
                   if k not in (COMPLETED, ACCEPTED_DRAFT, DISTILLED)):
                print(f"terminal: {term}; admission {rep['admission']}; "
                      f"conservation "
                      f"{'OK' if rep['conservation']['balanced'] else 'BROKEN'}")
            if engine is not None:
                print(f"draft engine: {engine.stats.as_dict()}")
            write_telemetry()
            if args.check_distilled:
                check_distilled(rep, stream=True)
            return

        for i, L in enumerate(sizes):
            sched.submit(seq_len=L, num_samples=1, seed=100 + i,
                         t0=None,          # None -> policy / default
                         tier=args.tier)
        results, rep = sched.run()
        print(f"\nscheduler: {rep['num_requests']} requests in "
              f"{rep['num_micro_batches']} micro-batches, "
              f"{rep['requests_per_s']:.2f} req/s, "
              f"overlap_eff={rep['overlap_efficiency']:.2f}, "
              f"mean NFE {rep['mean_request_nfe']:.1f}, "
              f"jit cache {rep['jit_cache']}")
        if t0_auto:
            print(f"adaptive t0 histogram: {rep['policy']['t0_histogram']}")
        if rep.get("speculative"):
            spec = rep["speculative"]
            print(f"speculative: {spec['accepted']}/{spec['eligible']} "
                  f"accepted (rate {spec['accept_rate']:.0%}, "
                  f"threshold {spec['accept_score']:.3f})")
        if rep.get("bandit"):
            print(f"bandit arms: {len(rep['bandit'])} contexts learned")
        if (rep.get("distilled") or {}).get("enabled"):
            d = rep["distilled"]
            print(f"distilled: {d['served']}/{d['requests']} served at "
                  f"NFE={d['nfe']} ({d['fallbacks']} quality-floor "
                  f"fallbacks, floor {d['gate_score']:.3f})")
        if engine is not None:
            print(f"draft engine: {engine.stats.as_dict()}")
        for rid in sorted(results)[:4]:
            r = results[rid]
            print(f"[{rid}] t0={r.t0:.2f} nfe={r.nfe} bucket={r.bucket_len} "
                  f"{decode(np.asarray(r.tokens[0]))}")
        write_telemetry()
        if args.check_distilled:
            check_distilled(rep, stream=False)
        return

    t0 = float(args.t0)
    if args.draft == "ar-kv":
        from repro.drafting import ARDraftEngine, LSTMDraftAdapter

        engine = ARDraftEngine(LSTMDraftAdapter(model=lstm), lparams,
                               max_len=args.seq_len)
        draft_generate = lambda rng, num: engine.generate_rows(
            jax.random.split(rng, num), args.seq_len)
    else:
        gen = jax.jit(lambda rng, num: lstm.generate(lparams, rng, num, args.seq_len),
                      static_argnums=1)
        draft_generate = lambda rng, num: gen(rng, num)
    step_fn = None
    if args.fused_step:
        from repro.kernels.ws_step import make_ws_step_fn
        step_fn = make_ws_step_fn(WarmStartPath(t0=t0))
    server = WarmStartServer(
        flow_model=model, flow_cfg=cfg, flow_params=state.params,
        draft_generate=draft_generate,
        path=WarmStartPath(t0=t0), cold_nfe=args.cold_nfe,
        step_fn=step_fn,
    )
    out, report = server.serve(jax.random.key(11), args.num)
    print(f"\nNFE: {report['nfe']} / cold {report['cold_nfe']} "
          f"(guaranteed x{report['speedup_report'].guaranteed_factor:.1f})")
    print(f"draft {report['draft_time_s']*1e3:.1f}ms "
          f"flow {report['flow_time_s']*1e3:.1f}ms "
          f"({report['per_nfe_s']*1e3:.1f}ms/NFE, one dispatch)")
    for i in range(min(args.num, 4)):
        print(f"[{i}] {decode(np.asarray(out[i]))}")


if __name__ == "__main__":
    main()
