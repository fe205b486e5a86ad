"""Continuous-batching warm-start serving engine.

Request-level front end over the paper's two-stage pipeline:

    queue -> pow2 seq buckets -> padded micro-batches
          -> [draft stage | flow refine stage]  (overlapped)
          -> per-request slices + guarantee reports

The two stages use *different* models (a lightweight draft generator and
the DFM flow backbone), so while the flow model refines micro-batch k on
the device, a host worker thread derives keys, dispatches and blocks on
the draft for micro-batch k+1 — the draft stage's host+device time hides
behind the refine stage instead of serialising with it.

The refine dispatch is ONE jitted ``lax.scan`` per micro-batch (the
shared :func:`repro.core.sampler.scan_refine_loop` body), compiled once
per ``(bucket_len, padded_rows, n_steps)`` — requests never retrace on
their own shapes. With a mesh, the refine runs sharded: weights TP over
``model`` (``SERVE_RULES`` via ``param_shardings``), batches over
``data``; without a mesh the single-device path is byte-for-byte the
plain jit.

Sampling is row-keyed (:func:`make_euler_one_step_rows`): every sample
row's PRNG stream is derived from its request's seed, so a request's
output is invariant to micro-batch packing.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import (
    Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guarantees
from repro.core.paths import WarmStartPath
from repro.core.sampler import (
    distill_schedule_rows, make_euler_one_step_rows, refine_schedule,
    refine_schedule_rows, scan_refine_loop, scan_refine_loop_rows,
)
from repro.serving.batcher import (
    ACCEPTED_DRAFT, CANCELLED, COMPLETED, DISTILL_STREAM, DISTILLED,
    DISTILLED_TIER, DRAFT_STREAM, FAILED, FLOW_STREAM, GUARANTEED_TIER,
    PRIORITY_CLASSES, SHED, TIMED_OUT, CancelToken, FillingBucket, MicroBatch,
    ServeRequest, bucket_seq_len, pack_requests, pad_rows, priority_rank,
    split_request, usable_rows,
)
from repro.serving.engine import (
    DispatchFailure, DispatchRetryPolicy, PerNFECostModel,
)
from repro.models.attention import record_attention
from repro.obs import MetricsRegistry, NullTracer, parse_metric_key


def _key_label(key: Any) -> str:
    """Compile key -> registry-label-safe string ((16, 4, 4) -> 16x4x4);
    metric labels may not contain commas or braces."""
    if isinstance(key, tuple):
        return "x".join(str(p) for p in key)
    return str(key)


def _key_from_label(label: str) -> str:
    """Inverse of :func:`_key_label` back to the report's str(tuple)."""
    parts = label.split("x")
    if len(parts) > 1:
        return f"({', '.join(parts)})"
    return label


def _profile_name(stage: str, k: Optional[int] = None) -> str:
    """A serving span's name on the profiler's host plane:
    ``serve.<stage>``, and ``#<k>`` where micro-batch ``k`` is known (the
    id its requests carry as ``CompletedRequest.micro_batch``)."""
    return f"serve.{stage}" if k is None else f"serve.{stage}#{k}"

# per-class SLO scaling for the streaming admission loop: a class's
# deadline is arrival + slo * factor; None disarms the deadline entirely
# (the class flushes only on full / idle / drain and is excluded from SLO
# attainment). This is the lever that trades best_effort p99 against
# premium attainment: premium deadlines are priced at face value while
# best_effort never forces a partial-bucket flush.
DEFAULT_CLASS_SLO_FACTOR: Dict[str, Optional[float]] = {
    "premium": 1.0,
    "standard": 1.0,
    "best_effort": None,
}


@dataclasses.dataclass(frozen=True)
class RequestResult:
    """Per-request output + the guarantee that was enforced for it.

    ``nfe`` is the request-level NFE bound ``warm_nfe(cold_nfe, t0)`` —
    with heterogeneous per-row t0 (``row_t0s`` non-empty) it is the
    WORST row's step count; deeper rows spent fewer. ``nfe == 0`` marks
    a speculatively ACCEPTED request: its draft cleared the acceptance
    probe and shipped with zero refine steps (``micro_batch == -1``,
    no guarantee machinery engaged — the guarantee holds vacuously)."""

    request_id: int
    tokens: np.ndarray              # (num_samples, seq_len) int32
    nfe: int
    t0: float
    bucket_len: int
    micro_batch: int
    row_t0s: Tuple[float, ...] = ()   # per-row t0 (per-row adaptive mode)


@dataclasses.dataclass(frozen=True)
class CompletedRequest(RequestResult):
    """A streamed result: the same payload as :class:`RequestResult`
    plus the request's admission/latency accounting. Yielded by
    :meth:`WarmStartScheduler.serve_stream` as each micro-batch
    finishes — the tokens are bit-identical to what the end-of-run batch
    path (:meth:`WarmStartScheduler.serve_requests`) returns for the
    same request.

    ``status`` is the request's terminal state
    (:data:`~repro.serving.batcher.TERMINAL_STATUSES`): every admitted
    request is yielded exactly once, and only ``COMPLETED`` results
    carry tokens — cancelled / timed-out / shed / failed requests are
    surfaced with an empty ``(0, seq_len)`` token array instead of
    being silently dropped."""

    arrival_s: float = 0.0          # admission time (stream clock)
    finished_s: float = 0.0         # micro-batch completion time
    latency_s: float = 0.0          # finished - arrival (time-to-result)
    flush_reason: str = ""          # full | deadline | idle | drain
    deadline_s: Optional[float] = None   # arrival + SLO (None: no SLO)
    slo_met: Optional[bool] = None       # finished <= deadline
    chunks: int = 1                 # micro-batch chunks reassembled
    status: str = COMPLETED         # terminal status (batcher constants)
    priority: str = "standard"      # the request's priority class


class _MonotonicClock:
    """Default stream clock; tests inject a fake with the same shape."""

    @staticmethod
    def time() -> float:
        return time.monotonic()

    @staticmethod
    def sleep(dt: float) -> None:
        time.sleep(dt)


# chunk request_ids are minted from here — far above any sane user id
# space, so a chunk id can never collide with an admitted request's id
_CHUNK_ID_BASE = 1 << 40


class QueueClosed(ValueError):
    """Submission to a closed :class:`AdmissionQueue`.

    Raised instead of silently enqueueing a request that the serving
    loop may never drain (the loop stops once the queue is closed AND
    empty). A ``ValueError`` subclass so pre-existing callers that
    caught ``ValueError`` keep working.
    """


class QueueFull(RuntimeError):
    """A bounded :class:`AdmissionQueue` rejected a submission.

    Raised when the queue is at ``max_depth`` and the incoming request's
    priority class is not strictly higher than the lowest class already
    queued — there is nothing cheaper to shed in its favour. The
    rejection is counted in :meth:`AdmissionQueue.stats` (``rejected``),
    so offered-load accounting stays exact.
    """


class AdmissionQueue:
    """Thread-safe request intake for :meth:`WarmStartScheduler
    .serve_stream` — the arrival side of the admission loop.

    Producers (an RPC front end, a replay thread) call :meth:`submit` or
    :meth:`push` while the stream is being served; the serving loop
    drains it between dispatches and keeps serving until the queue is
    :meth:`close`-d AND empty. Arrival timestamps default to the
    queue's clock at submission.

    **Bounded admission (overload hardening).** With ``max_depth`` set,
    the queue never holds more than that many requests: a submission to
    a full queue either *sheds* the most recent request of the lowest
    priority class present — but only when the incoming request's class
    is strictly higher (shedding never touches premium to admit
    best_effort) — or is *rejected* with :class:`QueueFull`. Shed
    requests are handed to the serving loop via :meth:`take_shed` and
    surface as ``SHED`` terminal results; :meth:`stats` keeps the exact
    conservation ledger (``offered == accepted + rejected``, with every
    accepted request later shed or drained exactly once).

    **Cancellation.** Every :meth:`submit` mints a
    :class:`~repro.serving.batcher.CancelToken` for its request
    (:meth:`push` attaches one if the request has none);
    :meth:`cancel` flips it by request_id at any point in the request's
    lifetime — still queued, waiting in a filling bucket, or already
    packed — and the serving loop resolves the request to a
    ``CANCELLED`` terminal status. Tokens are kept for the stream's
    lifetime so late cancels stay addressable.
    """

    _instances = itertools.count()

    def __init__(self, *, max_depth: Optional[int] = None, clock=None,
                 metrics: Optional[MetricsRegistry] = None):
        if max_depth is not None and max_depth < 1:
            raise ValueError(f"max_depth must be >= 1, got {max_depth}")
        self._clock = clock if clock is not None else _MonotonicClock()
        self._lock = threading.Lock()
        self._items: deque = deque()
        self._closed = False
        self._next_id = 0
        self.max_depth = max_depth
        self._tokens: Dict[int, CancelToken] = {}
        self._shed: List[ServeRequest] = []
        # the admission ledger lives in the metrics registry (the queue
        # is its owner — see docs/ARCHITECTURE.md metric ownership). A
        # shared registry serves several queues over its lifetime, so
        # each queue's counters carry a distinct `queue=` label and
        # stats() stays exact per queue.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._queue_label = f"q{next(AdmissionQueue._instances)}"
        q = self._queue_label
        self._c_offered = self.metrics.counter("admission.offered", queue=q)
        self._c_accepted = self.metrics.counter("admission.accepted", queue=q)
        self._c_rejected = self.metrics.counter("admission.rejected", queue=q)
        self._c_shed = self.metrics.counter("admission.shed", queue=q)
        self._g_depth = self.metrics.gauge("admission.queue_depth", queue=q)
        self._shed_classes: set = set()

    def _admit_locked(self, req: ServeRequest) -> None:
        """Depth-bounded enqueue; caller holds the lock. Counts the
        offer, then either enqueues, sheds a lower-class victim to make
        room, or raises QueueFull."""
        self._c_offered.inc()
        if self.max_depth is not None and len(self._items) >= self.max_depth:
            rank_in = priority_rank(req.priority)
            worst = max(priority_rank(r.priority) for r in self._items)
            if worst <= rank_in:
                self._c_rejected.inc()
                raise QueueFull(
                    f"admission queue full (depth {self.max_depth}) and "
                    f"request {req.request_id} ({req.priority}) does not "
                    f"outrank any queued request")
            # shed the NEWEST request of the worst class present: it has
            # the least sunk queueing time, and the class ordering means
            # premium is never shed before best_effort
            for i in range(len(self._items) - 1, -1, -1):
                if priority_rank(self._items[i].priority) == worst:
                    victim = self._items[i]
                    del self._items[i]
                    self._shed.append(victim)
                    self._c_shed.inc()
                    self._shed_classes.add(victim.priority)
                    self.metrics.counter(
                        "admission.shed_by_class", queue=self._queue_label,
                        priority=victim.priority).inc()
                    break
        self._c_accepted.inc()
        self._items.append(req)
        self._g_depth.set(len(self._items))

    def submit(self, *, seq_len: int, num_samples: int = 1, seed: int = 0,
               t0: Optional[float] = None, priority: str = "standard",
               timeout_s: Optional[float] = None,
               arrival_s: Optional[float] = None,
               tier: str = GUARANTEED_TIER) -> int:
        """Enqueue one request; returns its request_id.

        Raises :class:`QueueClosed` after :meth:`close`, and
        :class:`QueueFull` when a bounded queue has nothing cheaper to
        shed (see the class docstring for the shed-vs-reject rule).
        """
        with self._lock:
            if self._closed:
                raise QueueClosed("admission queue is closed")
            rid = self._next_id
            self._next_id += 1
            token = CancelToken()
            self._tokens[rid] = token
            self._admit_locked(ServeRequest(
                request_id=rid, seq_len=seq_len, num_samples=num_samples,
                seed=seed, t0=t0, priority=priority, timeout_s=timeout_s,
                cancel_token=token, tier=tier,
                arrival_s=(self._clock.time() if arrival_s is None
                           else arrival_s)))
        return rid

    def push(self, req: ServeRequest) -> int:
        """Enqueue a pre-built request (its request_id must be unique
        across the stream; the submitter owns that contract)."""
        with self._lock:
            if self._closed:
                raise QueueClosed("admission queue is closed")
            self._next_id = max(self._next_id, req.request_id + 1)
            if req.arrival_s == 0.0:
                req = dataclasses.replace(req, arrival_s=self._clock.time())
            if req.cancel_token is None:
                req = dataclasses.replace(req, cancel_token=CancelToken())
            self._tokens[req.request_id] = req.cancel_token
            self._admit_locked(req)
        return req.request_id

    def cancel(self, request_id: int) -> bool:
        """Cancel a request by id; returns False for unknown ids.

        Safe at any point in the lifecycle — queued, filling, packed, or
        already finished (then a no-op): the serving loop masks the
        request out wherever it currently is and yields a ``CANCELLED``
        terminal result, leaving every sibling request's output
        bit-identical to a run where this request was never submitted.
        """
        with self._lock:
            token = self._tokens.get(request_id)
        if token is None:
            return False
        token.cancel()
        return True

    def close(self) -> None:
        """No further arrivals; the serving loop drains and terminates."""
        with self._lock:
            self._closed = True

    def drain(self) -> List[ServeRequest]:
        with self._lock:
            items = list(self._items)
            self._items.clear()
            self._g_depth.set(0)
        return items

    def take_shed(self) -> List[ServeRequest]:
        """Hand over requests shed since the last call (serving loop
        yields them as ``SHED`` terminal results)."""
        with self._lock:
            shed, self._shed = self._shed, []
        return shed

    def stats(self) -> dict:
        """Exact admission ledger: ``offered == accepted + rejected``;
        shed requests are the subset of accepted ones later evicted.
        Every value is read from this queue's registry counters — the
        registry IS the ledger."""
        with self._lock:
            return {
                "offered": self._c_offered.value,
                "accepted": self._c_accepted.value,
                "rejected": self._c_rejected.value,
                "shed": self._c_shed.value,
                "shed_by_class": {
                    c: self.metrics.counter(
                        "admission.shed_by_class", queue=self._queue_label,
                        priority=c).value
                    for c in sorted(self._shed_classes)},
                "max_depth": self.max_depth,
            }

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed and not self._items

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


@partial(jax.jit, static_argnums=())
def _derive_row_keys(seeds: jax.Array, sample_idx: jax.Array):
    """(draft_keys, flow_keys), each (B,): fold (seed, sample index) into
    two independent streams. Depends only on the request's own seed and
    the row's index *within the request* — never on batch position."""

    def one(s, i):
        base = jax.random.fold_in(jax.random.key(s), i)
        return (jax.random.fold_in(base, DRAFT_STREAM),
                jax.random.fold_in(base, FLOW_STREAM))

    return jax.vmap(one)(seeds, sample_idx)


@partial(jax.jit, static_argnums=())
def _derive_distill_keys(seeds: jax.Array, sample_idx: jax.Array):
    """(B,) keys on the distilled tier's own stream (DISTILL_STREAM).

    Same (seed, sample index) folding as :func:`_derive_row_keys` but a
    third, disjoint stream: distilled sampling never consumes a key the
    guaranteed path's DRAFT/FLOW streams would, so a quality-floor
    fallback re-enters the guaranteed path with untouched streams —
    bit-identical to never having tried the distilled tier."""

    def one(s, i):
        base = jax.random.fold_in(jax.random.key(s), i)
        return jax.random.fold_in(base, DISTILL_STREAM)

    return jax.vmap(one)(seeds, sample_idx)


class WarmStartScheduler:
    """Request scheduler over the draft/flow warm-start pipeline.

    Args:
      flow_model: DFM backbone exposing ``dfm_apply(params, tokens, t)``.
      flow_params: backbone parameters (device_put sharded when ``mesh``).
      draft_fn: row-keyed draft generator ``(keys (B,), seq_len) ->
        (B, seq_len) int32`` (see :mod:`repro.serving.drafts`).
      cold_nfe: Euler steps of the cold-start baseline (step size 1/N).
      default_t0: warm-start time for requests without an override.
      temperature: softmax temperature of the refine step.
      max_rows / min_bucket / max_bucket / row_quantum: packing knobs
        (see :mod:`repro.serving.batcher`).
      overlap: run the draft stage of batch k+1 concurrently with the
        refine of batch k (off -> strictly serial, for debugging/timing).
      mesh: optional ``jax.sharding.Mesh``; enables the SERVE_RULES
        sharded refine dispatch. ``None`` is the single-device path.
      t0_policy: optional :class:`repro.drafting.AdaptiveT0Policy` or
        :class:`repro.drafting.BanditT0Policy` (the two share the policy
        protocol: ``scores_and_t0`` / ``t0_for_drafts`` + the
        ``calibration`` / ``bin_width`` / ``t0_floor`` attributes).
        When set, requests submitted WITHOUT a t0 override are drafted in
        a scoring pre-pass, their warm-start time chosen from measured
        draft quality (binned — see ``t0_bin_width``), and the pre-pass
        drafts are reused by the pipeline (never drafted twice). A
        bandit policy additionally receives an online reward per refined
        row: the probe re-run on the refined tokens (the verify step)
        minus the row's measured refine seconds priced by the per-NFE
        cost model.
      t0_bin_width: grouping bin for per-request t0 values (see
        ``batcher.pack_requests``); defaults to ``t0_policy.bin_width``
        when a policy is given, else 0 (exact-t0 grouping).
      per_row_t0: keep the pre-pass's per-ROW t0 vector instead of
        collapsing a request to its min — rows enter the shared masked
        refine scan at their OWN step index (the scan already supports
        heterogeneous entry), so a request with one poor and three good
        drafts no longer pays the poor row's step count on every row.
        The request-level guarantee bound stays ``warm_nfe(cold_nfe,
        min(row_t0s))``.
      speculative: enable the draft-and-verify fast path: after the
        scoring pre-pass, a request whose EVERY row's probe score clears
        ``accept_score`` ships its drafts directly — zero refine steps,
        terminal status ``ACCEPTED_DRAFT`` (batch path: ``nfe == 0``).
        Rejected requests re-pack into the normal (bucket, t0-bin,
        priority) warm-start path bit-identical to speculation-disabled
        serving: per-row fold_in PRNG streams and NFE schedules are
        functions of each request alone, and the same
        ``require_row_guarantees`` gate runs on every dispatch. Only
        requests WITHOUT a t0 override are eligible (an explicit t0 is a
        demand for refine; oversize chunks resolve their t0 at admission
        and are likewise never accepted). Requires ``t0_policy``.
      accept_score: speculative acceptance threshold on the probe score;
        ``None`` uses the policy's own (bandit) or the calibration's top
        anchor score (the pretty-good tier's mean).
      distilled_model / distilled_params: optional distilled few-step
        head (see :mod:`repro.drafting.distill`) enabling the
        ``tier="distilled"`` request class: K = ``distilled_nfe`` steps
        of the head instead of the full guaranteed refine, behind a
        probe-score quality floor. Needs ``t0_policy`` (the floor IS the
        policy's probe).
      distilled_nfe: steps the distilled tier runs (1 or 2).
      distilled_accept_score: the tier's quality floor — a distilled
        output whose min row probe score falls below it is re-served on
        the guaranteed path, bit-identical to a fresh guaranteed
        request. Defaults to ``accept_score`` (the speculative
        acceptance anchor).
      pair_buffer: optional :class:`repro.drafting.distill.PairBuffer`;
        when set, every guaranteed refine dispatch harvests its
        ``(draft, refined, t0)`` rows into it (the self-distillation
        training set — the guaranteed path is the teacher).
      tracer: optional :class:`repro.obs.SpanTracer` recording pipeline
        spans (draft worker, refine dispatch, scoring pre-pass, flush
        decisions) and per-request admission→terminal flow events for
        Perfetto export; with ``SpanTracer(profiler=True)`` the spans
        also land on the profiler's host plane as ``serve.<stage>#<k>``
        (see ``docs/ARCHITECTURE.md``). Defaults to the no-op
        :class:`repro.obs.NullTracer` — hot paths pay ~zero when off.
      metrics: optional :class:`repro.obs.MetricsRegistry`; the
        scheduler owns its serving counters there (terminal statuses,
        SLO, flush reasons, jit hit/miss, dispatch retries, speculative
        accepts) and ``stream_report`` sections are DERIVED from the
        registry. A fresh private registry is created when omitted.
    """

    def __init__(
        self,
        *,
        flow_model: Any,
        flow_params: Any,
        draft_fn: Callable[[jax.Array, int], jax.Array],
        cold_nfe: int,
        default_t0: float,
        temperature: float = 1.0,
        fused_block: int = 1,
        max_rows: int = 32,
        min_bucket: int = 8,
        max_bucket: Optional[int] = None,
        row_quantum: int = 4,
        overlap: bool = True,
        mesh: Optional[Any] = None,
        t0_policy: Optional[Any] = None,
        t0_bin_width: Optional[float] = None,
        retry_policy: Optional[DispatchRetryPolicy] = None,
        class_slo_factor: Optional[Dict[str, Optional[float]]] = None,
        per_row_t0: bool = False,
        speculative: bool = False,
        accept_score: Optional[float] = None,
        distilled_model: Optional[Any] = None,
        distilled_params: Optional[Any] = None,
        distilled_nfe: int = 1,
        distilled_accept_score: Optional[float] = None,
        pair_buffer: Optional[Any] = None,
        tracer: Optional[Any] = None,
        metrics: Optional[MetricsRegistry] = None,
    ):
        if cold_nfe < 1:
            raise ValueError(f"cold_nfe must be >= 1, got {cold_nfe}")
        if fused_block < 1:
            raise ValueError(f"fused_block must be >= 1, got {fused_block}")
        self.flow_model = flow_model
        self.draft_fn = draft_fn
        self.cold_nfe = cold_nfe
        self.default_t0 = default_t0
        self.temperature = temperature
        self.fused_block = fused_block
        self.max_rows = max_rows
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self.row_quantum = row_quantum
        self.overlap = overlap
        self.mesh = mesh
        self.t0_policy = t0_policy
        if t0_bin_width is None:
            t0_bin_width = (getattr(t0_policy, "bin_width", 0.0)
                            if t0_policy is not None else 0.0)
        self.t0_bin_width = float(t0_bin_width)
        self.per_row_t0 = bool(per_row_t0)
        self.speculative = bool(speculative)
        if self.speculative and t0_policy is None:
            raise ValueError(
                "speculative serving needs a t0_policy: acceptance is "
                "decided by the policy's quality probe")
        if accept_score is None and t0_policy is not None:
            accept_score = getattr(t0_policy, "accept_score", None)
            if accept_score is None:
                cal = getattr(t0_policy, "calibration", None)
                scores = getattr(cal, "scores", None)
                if scores:
                    accept_score = float(scores[-1])
        self.accept_score = (None if accept_score is None
                             else float(accept_score))
        if self.speculative and self.accept_score is None:
            raise ValueError(
                "speculative serving needs an accept_score (none given "
                "and the policy carries no calibration to derive one)")
        # distilled tier: a self-distilled K-step head served as a cheap
        # SLO class behind a calibrated probe-score quality floor
        self.distilled_model = distilled_model
        self.distilled_params = distilled_params
        self.distilled_nfe = int(distilled_nfe)
        self.pair_buffer = pair_buffer
        if distilled_model is not None:
            if not 1 <= self.distilled_nfe <= 2:
                raise ValueError(
                    f"distilled_nfe must be 1 or 2 (the tier's whole point "
                    f"is a 1-2 step refine), got {distilled_nfe}")
            if t0_policy is None:
                raise ValueError(
                    "the distilled tier needs a t0_policy: its quality "
                    "floor is the policy's probe score")
            if distilled_accept_score is None:
                distilled_accept_score = self.accept_score
            if distilled_accept_score is None:
                raise ValueError(
                    "distilled tier needs a quality floor "
                    "(distilled_accept_score, or a policy calibration to "
                    "derive one)")
        self.distilled_accept_score = (None if distilled_accept_score is None
                                       else float(distilled_accept_score))
        # bandit mode: the policy learns online from refined outcomes
        self._bandit_mode = (t0_policy is not None
                             and hasattr(t0_policy, "update")
                             and hasattr(t0_policy, "scorer"))
        # request_id -> (bucket_len, per-row draft probe scores): the
        # context each in-flight row's arm was selected under, consumed
        # when its refined reward is observed (bandit mode only)
        self._row_scores: Dict[int, Tuple[int, np.ndarray]] = {}

        # observability: spans into the (default no-op) tracer, counters
        # into the registry — run/stream reports are registry deltas
        self.tracer = tracer if tracer is not None else NullTracer()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        self._c_reward_probes = m.counter("bandit.reward_probes")
        self._c_spec_eligible = m.counter("speculative.eligible")
        self._c_spec_accepted = m.counter("speculative.accepted")
        self._c_cache_hits = m.counter("jit_cache.hits")
        self._c_cache_misses = m.counter("jit_cache.misses")
        self._c_fused_blocks = m.counter("fused.blocks_dispatched")
        self._c_fused_steps = m.counter("fused.steps_fused")
        self._c_dispatch_retries = m.counter("dispatch.retries")
        self._c_dispatch_failures = m.counter("dispatch.failures")
        self._c_distill_fallbacks = m.counter("distilled.fallbacks")
        self._c_distill_gate_evals = m.counter("distilled.gate_evals")
        self._c_distill_downgrades = m.counter("distilled.oversize_downgrades")
        if t0_policy is not None and hasattr(t0_policy, "bind_metrics"):
            t0_policy.bind_metrics(m)

        self._queue: List[ServeRequest] = []
        self._next_id = 0
        self._compiled: set = set()     # compile_key accounting
        # compile-key label -> the attention its refine trace took
        # ("fused" / "xla"), written while the program is traced
        self._attention: Dict[str, str] = {}
        # measured latency oracle for the SLO admission loop: per-NFE
        # refine cost EWMA per compile key (+ global fallback), fed by
        # every _stage_refine dispatch; draft-stage cost EWMA beside it
        self.cost_model = PerNFECostModel(metrics=m)
        self._draft_cost_ewma: Optional[float] = None
        self._chunk_ids = itertools.count(_CHUNK_ID_BASE)
        self.stream_report: Optional[dict] = None
        # dispatch fault isolation: a failed refine dispatch retries with
        # bounded exponential backoff, then fails ONLY its own requests
        self.retry_policy = (retry_policy if retry_policy is not None
                             else DispatchRetryPolicy())
        self.class_slo_factor = dict(DEFAULT_CLASS_SLO_FACTOR)
        if class_slo_factor:
            for cls, factor in class_slo_factor.items():
                priority_rank(cls)      # raises on unknown classes
                self.class_slo_factor[cls] = factor
        # test-only fault injection: when set, called as hook(mb, attempt)
        # immediately before every refine dispatch attempt; raising from
        # it makes that attempt fail exactly like a device fault would
        self._dispatch_fault_hook: Optional[Callable[[Any, int], None]] = None
        # the active stream's clock (serve_stream installs it) so retry
        # backoff sleeps on the SAME clock the tests drive
        self._stream_clock: Optional[Any] = None

        # velocity_scale is t0-independent for the linear schedule, so one
        # stepping path serves every per-request t0 (the t0 only moves the
        # per-row (ts, hs, active, key_idx) schedule, a dynamic input).
        one_step = make_euler_one_step_rows(
            WarmStartPath(t0=0.0), temperature=temperature)
        fused_fn = None
        if fused_block > 1:
            from repro.kernels import make_ws_fused_fn
            fused_fn = make_ws_fused_fn(WarmStartPath(t0=0.0),
                                        temperature=temperature)

        def refine(params, flow_keys, x, ts, hs, active, key_idx):
            # masked per-row loop: rows enter the shared scan at their own
            # step index; a t0-homogeneous batch reduces bit-exactly to
            # the plain scan_refine_loop schedule.
            logits_fn = lambda xt, tb: self.flow_model.dfm_apply(params, xt, tb)
            with record_attention() as taken:
                out = scan_refine_loop_rows(
                    logits_fn, one_step, x, flow_keys, ts, hs, active,
                    key_idx, fused_block=fused_block, fused_fn=fused_fn)
            if taken:   # runs only while tracing: key (bucket, rows, steps)
                key = (x.shape[1], x.shape[0], ts.shape[0])
                self._attention[_key_label(key)] = "+".join(sorted(set(taken)))
            return out

        # donate the draft token buffer into the refine loop off-CPU, as
        # the one-shot engine does — it is dead after the dispatch
        donate = () if jax.default_backend() == "cpu" else (2,)
        if mesh is None:
            self.flow_params = flow_params
            self._row_multiple = 1
            self._refine_loop = jax.jit(refine, donate_argnums=donate)
        else:
            from repro.distributed import sharding as shd

            self._param_shardings = shd.param_shardings(
                flow_params, shd.SERVE_RULES, mesh)
            self.flow_params = jax.device_put(flow_params, self._param_shardings)
            self._row_multiple = shd.batch_axis_size(mesh)
            rows1 = shd.batch_sharding(mesh, 1)
            rows2 = shd.batch_sharding(mesh, 2)
            repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

            def refine_sharded(params, flow_keys, x, ts, hs, active, key_idx):
                # rules in scope at trace time so model-internal
                # `constrain` annotations resolve against SERVE_RULES
                with shd.axis_rules(shd.SERVE_RULES, mesh):
                    return refine(params, flow_keys, x, ts, hs, active, key_idx)

            self._refine_loop = jax.jit(
                refine_sharded,
                in_shardings=(self._param_shardings, rows1, rows2,
                              repl, repl, repl, repl),
                out_shardings=rows2,
                donate_argnums=donate,
            )

        # distilled tier: the SAME masked row scan, the distilled head's
        # logits, a K-step schedule, and a third key stream
        # (DISTILL_STREAM) — so a fallback request's guaranteed refine
        # consumes exactly the keys a fresh guaranteed request would.
        # The head is tiny; it runs unsharded even under a mesh.
        if distilled_model is not None:
            def distill(params, keys, x, ts, hs, active, key_idx):
                logits_fn = lambda xt, tb: distilled_model.dfm_apply(
                    params, xt, tb)
                return scan_refine_loop_rows(
                    logits_fn, one_step, x, keys, ts, hs, active, key_idx)

            self._distill_loop = jax.jit(distill, donate_argnums=donate)
        else:
            self._distill_loop = None

    # ---- registry-backed counter views (lifetime totals) -----------------

    @property
    def _cache_hits(self) -> int:
        return self._c_cache_hits.value

    @property
    def _cache_misses(self) -> int:
        return self._c_cache_misses.value

    @property
    def _dispatch_retries(self) -> int:
        return self._c_dispatch_retries.value

    @property
    def _dispatch_failures(self) -> int:
        return self._c_dispatch_failures.value

    @property
    def _spec_eligible(self) -> int:
        return self._c_spec_eligible.value

    @property
    def _spec_accepted(self) -> int:
        return self._c_spec_accepted.value

    @property
    def _reward_probes(self) -> int:
        return self._c_reward_probes.value

    # ---- request intake --------------------------------------------------

    def submit(self, *, seq_len: int, num_samples: int = 1, seed: int = 0,
               t0: Optional[float] = None, tier: str = GUARANTEED_TIER) -> int:
        """Enqueue one request; returns its request_id.

        ``t0=None`` means "engine decides": the adaptive policy scores
        the request's drafts when ``t0_policy`` is set, else
        ``default_t0``. An explicit t0 is always honoured verbatim (and
        never scored).

        ``tier="distilled"`` asks for the cheap K-step distilled head
        behind its quality floor (needs ``distilled_model``); it falls
        back to the guaranteed path when the floor rejects the output.

        Rejects unservable requests HERE (bucket overflow, too many
        samples) so one bad request can never poison a queued batch.
        """
        bucket_seq_len(seq_len, min_bucket=self.min_bucket,
                       max_bucket=self.max_bucket)
        unit = math.lcm(self.row_quantum, self._row_multiple)
        if pad_rows(num_samples, unit) > self.max_rows:
            raise ValueError(
                f"num_samples {num_samples} pads to "
                f"{pad_rows(num_samples, unit)} rows > max_rows "
                f"{self.max_rows} (split the request)")
        if tier == DISTILLED_TIER and self._distill_loop is None:
            raise ValueError(
                "tier='distilled' needs distilled_model/distilled_params "
                "on the scheduler")
        rid = self._next_id
        self._next_id += 1
        self._queue.append(ServeRequest(
            request_id=rid, seq_len=seq_len, num_samples=num_samples,
            seed=seed, t0=t0, tier=tier))
        return rid

    # ---- stages ----------------------------------------------------------

    def _mb_row_streams(self, mb: MicroBatch):
        """(seeds, idx) int32 arrays deriving the per-row key streams."""
        # int32 end to end — ServeRequest rejects seeds outside [0, 2**31)
        seeds = np.zeros((mb.padded_rows,), np.int32)
        idx = np.zeros((mb.padded_rows,), np.int32)
        for span in mb.spans:
            for r in range(span.rows):
                seeds[span.row_offset + r] = span.request.seed
                # oversize-split chunks keep their rows' ORIGINAL sample
                # indices (sample_offset), so a chunk row's PRNG stream
                # is the one the unsplit request would have used
                idx[span.row_offset + r] = span.request.sample_offset + r
        # padding rows: deterministic dummy stream (seed 0, descending
        # negative sample indices can't collide with real rows of seed 0)
        for r in range(mb.rows, mb.padded_rows):
            seeds[r], idx[r] = 0, -(r + 1)
        return seeds, idx

    def _stage_keys_and_draft(self, mb: MicroBatch,
                              predrafted: Optional[Dict[int, np.ndarray]] = None,
                              k: Optional[int] = None):
        """Draft stage for one micro-batch ``k`` (runs on the worker
        thread): derive per-row keys, generate drafts at bucket length,
        block.

        ``predrafted`` (adaptive-t0 mode) maps request_id -> that
        request's (num_samples, bucket_len) drafts from the scoring
        pre-pass; they are assembled instead of re-drafted (the pre-pass
        used the same per-row keys, so the tokens are identical either
        way — padding rows just stay zero).
        """
        with self.tracer.span("draft", track="draft_worker",
                              profile=_profile_name("draft", k),
                              micro_batch=k, bucket=mb.bucket_len,
                              rows=mb.rows,
                              predrafted=predrafted is not None):
            t0 = time.perf_counter()
            seeds, idx = self._mb_row_streams(mb)
            draft_keys, flow_keys = _derive_row_keys(
                jnp.asarray(seeds), jnp.asarray(idx))
            if predrafted is not None:
                x = np.zeros((mb.padded_rows, mb.bucket_len), np.int32)
                for span in mb.spans:
                    x[span.row_offset:span.row_offset + span.rows] = \
                        predrafted[span.request.request_id]
                x = jnp.asarray(x)
            else:
                x = self.draft_fn(draft_keys, mb.bucket_len)
            x = jax.block_until_ready(x)
            t_draft = time.perf_counter() - t0
            self._draft_cost_ewma = (
                t_draft if self._draft_cost_ewma is None
                else 0.7 * self._draft_cost_ewma + 0.3 * t_draft)
            self.metrics.gauge("draft.cost_ewma_s").set(self._draft_cost_ewma)
        return x, flow_keys, t_draft

    def _dispatch_refine(self, mb: MicroBatch, x, flow_keys, ts, hs,
                         active, key_idx, k: Optional[int] = None):
        """The jit-cache dispatch wrapper: one refine-loop dispatch with
        bounded-backoff retries (:class:`DispatchRetryPolicy`). Each
        attempt's jitted call and wait is the ``dispatch`` span.

        The refine loop DONATES the token buffer off-CPU, so a retry
        cannot replay the same device array — when retries are possible
        on a donating backend, the drafts are snapshotted to host memory
        first and every retry re-uploads from that snapshot. Raises
        :class:`DispatchFailure` once the budget is exhausted; the
        streaming loop turns that into ``FAILED`` terminal results for
        this micro-batch only, the batch path re-queues.
        """
        policy = self.retry_policy
        x_backup = None
        if policy.max_retries > 0 and jax.default_backend() != "cpu":
            x_backup = np.asarray(x)
        for attempt in range(policy.attempts):
            try:
                if self._dispatch_fault_hook is not None:
                    self._dispatch_fault_hook(mb, attempt)
                if attempt > 0 and x_backup is not None:
                    x = jnp.asarray(x_backup)
                with self.tracer.span("dispatch", track="refine_dispatch",
                                      profile=_profile_name("dispatch", k),
                                      micro_batch=k, attempt=attempt):
                    out = self._refine_loop(
                        self.flow_params, flow_keys, x, jnp.asarray(ts),
                        jnp.asarray(hs), jnp.asarray(active),
                        jnp.asarray(key_idx))
                    return jax.block_until_ready(out)
            except Exception as err:  # noqa: BLE001 — device faults vary
                if attempt >= policy.max_retries:
                    self._c_dispatch_failures.inc()
                    raise DispatchFailure(
                        mb.compile_key, attempt + 1, err) from err
                self._c_dispatch_retries.inc()
                sleep = (self._stream_clock.sleep
                         if self._stream_clock is not None else time.sleep)
                sleep(policy.backoff_s(attempt))
        raise AssertionError("unreachable")  # pragma: no cover

    def _stage_refine(self, mb: MicroBatch, x, flow_keys,
                      k: Optional[int] = None):
        """Flow stage for micro-batch ``k``: one jitted scan dispatch over
        the per-row masked schedule. Distilled-tier micro-batches route
        to :meth:`_stage_distill` instead."""
        if mb.tier == DISTILLED_TIER:
            return self._stage_distill(mb, x, k)
        harvest = None
        if self.pair_buffer is not None:
            # snapshot the drafts BEFORE dispatch: the refine loop
            # donates the token buffer off-CPU
            harvest = np.asarray(x)
        span = self.tracer.span("refine", track="refine_dispatch",
                                profile=_profile_name("refine", k),
                                micro_batch=k, bucket=mb.bucket_len,
                                rows=mb.rows,
                                padded_rows=mb.padded_rows, tier=mb.tier,
                                key=str(mb.compile_key))
        with span as sp:
            t0 = time.perf_counter()
            key = mb.compile_key
            if key in self._compiled:
                self._c_cache_hits.inc()
                self.metrics.counter("jit_cache.per_key",
                                     key=_key_label(key), kind="hit").inc()
                was_miss = False
            else:
                self._compiled.add(key)
                self._c_cache_misses.inc()
                self.metrics.counter("jit_cache.per_key",
                                     key=_key_label(key), kind="miss").inc()
                was_miss = True
            sp["cache"] = "miss" if was_miss else "hit"
            ts, hs, active, key_idx, nfe_rows = refine_schedule_rows(
                mb.row_t0s, 1.0 / self.cold_nfe, self.cold_nfe)
            sp["nfe"] = len(ts)
            if self.fused_block > 1:
                blk = min(self.fused_block, len(ts))
                self._c_fused_blocks.inc(-(-len(ts) // blk))
                self._c_fused_steps.inc(len(ts))
            x = self._dispatch_refine(mb, x, flow_keys, ts, hs, active,
                                      key_idx, k)
            # observed NFE = what the executed schedule actually spent:
            # the scan length for the batch (cross-checked against an
            # independent warm_nfe(cold_nfe, min t0) recomputation — the
            # worst-case 1/(1 - min t0) guarantee), and per ROW the
            # active-step count, which must equal each row's own
            # warm_nfe(cold_nfe, t0_row). A batcher/schedule regression
            # (wrong n_steps, wrong grouping, stale cold_nfe, a row
            # overshooting its bound) raises here.
            guarantees.require_bucket_guarantee(
                self.cold_nfe, mb.t0, len(ts),
                bucket_len=mb.bucket_len, rows=mb.rows)
            observed_rows = active.sum(axis=0)
            mask = mb.row_mask
            guarantees.require_row_guarantees(
                self.cold_nfe, mb.row_t0s[mask], observed_rows[mask],
                bucket_len=mb.bucket_len, rows=mb.rows)
            t_flow = time.perf_counter() - t0
            self.cost_model.observe(key, t_flow, len(ts), compiled=was_miss)
            # bandit verify step AFTER the cost observation so the reward
            # probe's own time never poisons the per-NFE refine EWMA
            if self._bandit_mode and self._row_scores:
                with self.tracer.span("reward_probe", track="refine_dispatch",
                                      bucket=mb.bucket_len):
                    self._observe_rewards(mb, x)
            # self-distillation harvest, also after the cost observation:
            # every guaranteed dispatch feeds (draft, refined, t0) rows to
            # the pair buffer — the guaranteed path IS the teacher, no
            # extra forward passes
            if harvest is not None:
                self.pair_buffer.add_batch(
                    harvest, np.asarray(x), mb.row_t0s, mask=mb.row_mask)
        return x, t_flow

    def _stage_distill(self, mb: MicroBatch, x, k: Optional[int] = None):
        """Distilled-tier flow stage: K = ``distilled_nfe`` steps of the
        distilled head through the same masked row scan, keyed on the
        disjoint DISTILL_STREAM. No NFE-guarantee gates run here — the
        tier's contract is the probe-score quality floor (checked by the
        caller via :meth:`_distill_gate`), not a schedule bound."""
        span = self.tracer.span("distill", track="refine_dispatch",
                                profile=_profile_name("distill", k),
                                micro_batch=k, bucket=mb.bucket_len,
                                rows=mb.rows,
                                padded_rows=mb.padded_rows, tier=mb.tier,
                                key=str(mb.compile_key))
        with span as sp:
            t0 = time.perf_counter()
            key = mb.compile_key
            if key in self._compiled:
                self._c_cache_hits.inc()
                self.metrics.counter("jit_cache.per_key",
                                     key=_key_label(key), kind="hit").inc()
                was_miss = False
            else:
                self._compiled.add(key)
                self._c_cache_misses.inc()
                self.metrics.counter("jit_cache.per_key",
                                     key=_key_label(key), kind="miss").inc()
                was_miss = True
            sp["cache"] = "miss" if was_miss else "hit"
            ts, hs, active, key_idx, _ = distill_schedule_rows(
                mb.row_t0s, self.distilled_nfe)
            sp["nfe"] = len(ts)
            seeds, idx = self._mb_row_streams(mb)
            dkeys = _derive_distill_keys(jnp.asarray(seeds), jnp.asarray(idx))
            try:
                with self.tracer.span("dispatch", track="refine_dispatch",
                                      profile=_profile_name("dispatch", k),
                                      micro_batch=k, attempt=0):
                    out = self._distill_loop(
                        self.distilled_params, dkeys, x, jnp.asarray(ts),
                        jnp.asarray(hs), jnp.asarray(active),
                        jnp.asarray(key_idx))
                    x = jax.block_until_ready(out)
            except Exception as err:  # noqa: BLE001 — device faults vary
                self._c_dispatch_failures.inc()
                raise DispatchFailure(mb.compile_key, 1, err) from err
            t_flow = time.perf_counter() - t0
            self.cost_model.observe(key, t_flow, len(ts), compiled=was_miss)
        return x, t_flow

    def _distill_gate(self, mb: MicroBatch, x) -> Dict[int, Tuple[bool, float]]:
        """The distilled tier's quality floor: score the distilled output
        rows with the policy's probe and compare each REQUEST's minimum
        row score against ``distilled_accept_score`` (the same min-over-
        rows shape as speculative acceptance). Returns
        ``request_id -> (passed, min_score)``; failing requests fall back
        to the guaranteed path."""
        self._c_distill_gate_evals.inc()
        scores = np.asarray(self.t0_policy.scorer(x))
        out: Dict[int, Tuple[bool, float]] = {}
        for span in mb.spans:
            rs = scores[span.row_offset:span.row_offset + span.rows]
            mn = float(rs.min())
            out[span.request.request_id] = (
                mn >= self.distilled_accept_score, mn)
        return out

    def _observe_rewards(self, mb: MicroBatch, x) -> None:
        """Bandit reward observation for one refined micro-batch (the
        VERIFY step): re-run the quality probe on the refined tokens
        (one backbone evaluation per micro-batch, amortised over all its
        rows) and feed each row's arm the refined score minus that row's
        refine seconds priced by the measured per-NFE cost model — the
        bandit optimizes measured wall time, not a step-count proxy.
        Rows whose (bucket, draft-score) context was not recorded in the
        pre-pass (explicit-t0 requests, chunks) are skipped."""
        pending = [(span, self._row_scores.pop(span.request.request_id))
                   for span in mb.spans
                   if span.request.request_id in self._row_scores]
        if not pending:
            return
        refined = np.asarray(self.t0_policy.scorer(x))
        self._c_reward_probes.inc()
        row_t0s = mb.row_t0s
        cold_s = self.cost_model.cost_for_nfe(self.cold_nfe)
        for span, (blen, draft_scores) in pending:
            for r in range(span.rows):
                t0r = float(row_t0s[span.row_offset + r])
                nfe_r = guarantees.warm_nfe(self.cold_nfe, t0r)
                row_s = self.cost_model.cost_for_nfe(nfe_r, mb.compile_key)
                if row_s is not None and cold_s:
                    cost_norm = row_s / cold_s
                else:
                    cost_norm = nfe_r / self.cold_nfe
                self.t0_policy.update(
                    blen, float(draft_scores[r]), t0r,
                    quality_score=float(refined[span.row_offset + r]),
                    cost_norm=cost_norm)

    # ---- jit-cache / fused-dispatch reporting ----------------------------

    def _jit_cache_snapshot(self):
        """Registry snapshot so each run/stream reports its OWN deltas
        (lifetime totals stay in the metrics registry)."""
        return self.metrics.snapshot()

    def _jit_cache_delta(self, snap) -> dict:
        """The report's ``jit_cache`` section, derived from registry
        counter deltas since ``snap``: aggregate + per-compile-key
        hit/miss counts (with the attention each refine key's trace took,
        ``"fused"`` or ``"xla"``) and fused-block dispatch totals."""
        deltas = self.metrics.counter_deltas(snap)
        per_key: Dict[str, Dict[str, Any]] = {}
        for mkey, v in deltas.items():
            name, labels = parse_metric_key(mkey)
            if name != "jit_cache.per_key":
                continue
            entry = per_key.setdefault(
                _key_from_label(labels["key"]), {"hits": 0, "misses": 0})
            entry["hits" if labels["kind"] == "hit" else "misses"] += v
            if labels["key"] in self._attention:
                entry["attention"] = self._attention[labels["key"]]
        return {
            "hits": deltas.get("jit_cache.hits", 0),
            "misses": deltas.get("jit_cache.misses", 0),
            "per_key": dict(sorted(per_key.items())),
            "fused": {
                "fused_block": self.fused_block,
                "blocks_dispatched": deltas.get("fused.blocks_dispatched", 0),
                "steps_fused": deltas.get("fused.steps_fused", 0),
            },
        }

    # ---- the pipeline ----------------------------------------------------

    def run(self) -> Tuple[Dict[int, RequestResult], dict]:
        """Drain the queue through the overlapped two-stage pipeline.

        Returns ``(results, report)``: per-request results keyed by
        request_id, and an engine report with per-batch stage latencies,
        overlap efficiency, throughput and jit-cache counters.
        """
        requests, self._queue = self._queue, []
        try:
            return self.serve_requests(requests)
        except Exception:
            # put the unserved requests back so a failure is retryable
            self._queue = requests + self._queue
            raise

    def _policy_prepass(self, requests: Sequence[ServeRequest]):
        """Traced wrapper for :meth:`_policy_prepass_inner` (the span
        carries the scored/accepted counts for the Perfetto view)."""
        with self.tracer.span("scoring_prepass", track="scoring",
                              requests=len(requests)) as sp:
            out = self._policy_prepass_inner(requests)
            sp["scored"] = out[2]["scored_requests"]
            sp["accepted"] = len(out[3])
        return out

    def _policy_prepass_inner(self, requests: Sequence[ServeRequest]):
        """Adaptive-t0 scoring pre-pass (t0_policy mode).

        Drafts every request at its bucket length (row-keyed, batched per
        bucket), scores the drafts of requests WITHOUT a t0 override, and
        resolves their warm-start time through the policy. Returns
        ``(resolved_requests, predrafted, policy_report, accepted)`` —
        the drafts are kept and reused by the pipeline (requests are
        never drafted twice), identical to what the draft stage would
        have produced because the pre-pass derives the same per-row key
        streams.

        **Speculative accept/reject** (``speculative=True``): a scored
        request whose EVERY row's probe score clears ``accept_score`` is
        pulled out of ``resolved_requests`` and returned in ``accepted``
        (``[{"request", "tokens", "t0", "scores"}]`` — tokens at bucket
        length); it never packs, never refines, never touches the PRNG
        or schedule of any other request. Rejected requests resolve
        exactly as with speculation off: the policy selects their t0
        BEFORE any accept decision is applied, so a rejected request's
        (t0, keys, schedule) — and therefore its output bytes — are
        identical to a speculation-disabled run.

        In bandit mode the pre-pass also records each scored row's
        (bucket, draft-score) context for the reward observed when its
        refined micro-batch completes, and credits acceptances to the
        bandit's accept counters.
        """
        t_start = time.perf_counter()
        by_bucket: Dict[int, List[ServeRequest]] = {}
        for req in requests:
            blen = bucket_seq_len(req.seq_len, min_bucket=self.min_bucket,
                                  max_bucket=self.max_bucket)
            by_bucket.setdefault(blen, []).append(req)

        predrafted: Dict[int, np.ndarray] = {}
        resolved_t0: Dict[int, float] = {}
        resolved_rows: Dict[int, Tuple[float, ...]] = {}
        accepted_info: Dict[int, dict] = {}
        scored = 0
        eligible = 0
        for blen, reqs in sorted(by_bucket.items()):
            seeds, idx, offsets = [], [], {}
            for req in reqs:
                offsets[req.request_id] = len(seeds)
                seeds.extend([req.seed] * req.num_samples)
                idx.extend(range(req.sample_offset,
                                 req.sample_offset + req.num_samples))
            draft_keys, _ = _derive_row_keys(
                jnp.asarray(np.asarray(seeds, np.int32)),
                jnp.asarray(np.asarray(idx, np.int32)))
            x = np.asarray(jax.block_until_ready(self.draft_fn(draft_keys, blen)))
            need_score = [r for r in reqs if r.t0 is None]
            if need_score:
                rows = np.concatenate([
                    x[offsets[r.request_id]:offsets[r.request_id] + r.num_samples]
                    for r in need_score])
                if hasattr(self.t0_policy, "scores_and_t0"):
                    scores_rows, t0_rows = \
                        self.t0_policy.scores_and_t0(rows)
                else:
                    scores_rows = None
                    t0_rows = self.t0_policy.t0_for_drafts(rows)
                at = 0
                for r in need_score:
                    rs = t0_rows[at:at + r.num_samples]
                    sc = (None if scores_rows is None
                          else scores_rows[at:at + r.num_samples])
                    at += r.num_samples
                    # distilled-tier requests are never speculatively
                    # accepted: their cheap path is the distilled head
                    # (quality-gated AFTER it runs), and excluding them
                    # keeps the guaranteed path's accept stream identical
                    # with the tier on or off
                    if (self.speculative and sc is not None
                            and r.tier != DISTILLED_TIER):
                        eligible += 1
                        if float(sc.min()) >= self.accept_score:
                            accepted_info[r.request_id] = {
                                "t0": float(rs.min()),
                                "scores": np.array(sc),
                            }
                            if self._bandit_mode:
                                for s in sc:
                                    self.t0_policy.observe_accept(
                                        blen, float(s))
                            continue
                    if (self._bandit_mode and sc is not None
                            and r.tier != DISTILLED_TIER):
                        self._row_scores[r.request_id] = (blen, np.array(sc))
                    if self.per_row_t0:
                        resolved_rows[r.request_id] = tuple(
                            float(v) for v in rs)
                    resolved_t0[r.request_id] = float(rs.min())
                scored += len(need_score)
            for req in reqs:
                o = offsets[req.request_id]
                predrafted[req.request_id] = x[o:o + req.num_samples]

        resolved: List[ServeRequest] = []
        accepted: List[dict] = []
        for req in requests:
            info = accepted_info.get(req.request_id)
            if info is not None:
                accepted.append({
                    "request": req,
                    "tokens": predrafted[req.request_id],
                    "t0": info["t0"],
                    "scores": info["scores"],
                })
                continue
            if req.t0 is not None:
                resolved.append(req)
            else:
                resolved.append(dataclasses.replace(
                    req, t0=resolved_t0[req.request_id],
                    row_t0s=resolved_rows.get(req.request_id, ())))
        self.metrics.counter("policy.scored_requests").inc(scored)
        self._c_spec_eligible.inc(eligible)
        self._c_spec_accepted.inc(len(accepted))
        report = {
            "scored_requests": scored,
            "prepass_time_s": time.perf_counter() - t_start,
            "t0_histogram": dict(sorted(_histogram(
                list(resolved_t0.values())).items())),
            "speculative": (None if not self.speculative else {
                "eligible": eligible,
                "accepted": len(accepted),
                "accept_score": self.accept_score,
            }),
        }
        return resolved, predrafted, report, accepted

    def serve_requests(
        self, requests: Sequence[ServeRequest]
    ) -> Tuple[Dict[int, RequestResult], dict]:
        # the wall clock starts BEFORE the policy pre-pass: in adaptive
        # mode the pre-pass IS the draft stage (plus scoring), so
        # wall_time_s / requests_per_s must pay for it
        wall0 = time.perf_counter()
        policy_report = None
        accepted: List[dict] = []
        # as-submitted requests, pre-resolution: a distilled request that
        # fails its quality floor re-enters the guaranteed path from THIS
        # object (t0 unresolved again), so the fallback round is
        # indistinguishable from a fresh guaranteed submission
        originals = {r.request_id: r for r in requests}
        results: Dict[int, RequestResult] = {}
        batch_reports: List[dict] = []
        cache_snap = self._jit_cache_snapshot()
        draft_total = 0.0
        flow_total = 0.0
        all_batches: List[MicroBatch] = []
        distill_stats = {"requests": 0, "served": 0, "fallbacks": 0,
                         "min_served_score": None}
        fallback: List[ServeRequest] = []

        def finish(k: int, mb: MicroBatch, x, t_draft: float, t_flow: float):
            nonlocal draft_total, flow_total
            draft_total += t_draft
            flow_total += t_flow
            gate = (self._distill_gate(mb, x)
                    if mb.tier == DISTILLED_TIER else None)
            x_host = np.asarray(x)
            for span, span_t0, span_rows in zip(mb.spans, mb.t0_spans,
                                                mb.row_t0_spans):
                req = span.request
                if gate is not None:
                    passed, mn = gate[req.request_id]
                    if not passed:
                        self._c_distill_fallbacks.inc()
                        distill_stats["fallbacks"] += 1
                        fallback.append(dataclasses.replace(
                            originals[req.request_id], tier=GUARANTEED_TIER))
                        continue
                    distill_stats["served"] += 1
                    ms = distill_stats["min_served_score"]
                    distill_stats["min_served_score"] = (
                        mn if ms is None else min(ms, mn))
                    results[req.request_id] = RequestResult(
                        request_id=req.request_id,
                        tokens=x_host[span.row_offset:
                                      span.row_offset + span.rows,
                                      :req.seq_len],
                        nfe=self.distilled_nfe, t0=span_t0,
                        bucket_len=mb.bucket_len, micro_batch=k)
                    continue
                results[req.request_id] = RequestResult(
                    request_id=req.request_id,
                    tokens=x_host[span.row_offset:span.row_offset + span.rows,
                                  :req.seq_len],
                    nfe=guarantees.warm_nfe(self.cold_nfe, span_t0),
                    t0=span_t0,
                    bucket_len=mb.bucket_len, micro_batch=k,
                    row_t0s=span_rows)
            batch_reports.append({
                "micro_batch": k,
                "bucket_len": mb.bucket_len,
                "rows": mb.rows,
                "padded_rows": mb.padded_rows,
                "t0": mb.t0,
                "t0_spans": list(mb.t0_spans),
                "nfe": mb.n_steps,
                "tier": mb.tier,
                "draft_time_s": t_draft,
                "flow_time_s": t_flow,
            })

        # round 0 serves the submitted mix; round 1 (only reached when a
        # distilled request misses its quality floor) re-serves the
        # fallbacks as guaranteed requests — they are guaranteed-tier by
        # construction, so the loop terminates after at most two rounds
        pending = list(requests)
        while pending:
            distill_stats["requests"] += sum(
                1 for r in pending if r.tier == DISTILLED_TIER)
            predrafted = None
            if self.t0_policy is not None:
                pending_resolved, predrafted, pr, acc_round = \
                    self._policy_prepass(pending)
                accepted.extend(acc_round)
                if policy_report is None:
                    policy_report = pr
                else:
                    policy_report["scored_requests"] += pr["scored_requests"]
                    policy_report["prepass_time_s"] += pr["prepass_time_s"]
                    if (policy_report.get("speculative")
                            and pr.get("speculative")):
                        for f in ("eligible", "accepted"):
                            policy_report["speculative"][f] += \
                                pr["speculative"][f]
                # pre-pass drafting+scoring counts as draft-stage time; it
                # is serial (never hidden behind a refine), which the
                # overlap arithmetic below reflects automatically since it
                # sits in both draft_total and the wall clock
                draft_total += pr["prepass_time_s"]
            else:
                pending_resolved = list(pending)

            batches = pack_requests(
                pending_resolved, cold_nfe=self.cold_nfe,
                default_t0=self.default_t0,
                max_rows=self.max_rows, min_bucket=self.min_bucket,
                max_bucket=self.max_bucket, row_quantum=self.row_quantum,
                row_multiple=self._row_multiple,
                t0_bin_width=self.t0_bin_width,
                distilled_nfe=self.distilled_nfe)
            k0 = len(all_batches)
            all_batches.extend(batches)

            stage_draft = partial(self._stage_keys_and_draft,
                                  predrafted=predrafted)
            if not self.overlap or len(batches) <= 1:
                for k, mb in enumerate(batches):
                    x, flow_keys, t_draft = stage_draft(mb)
                    x, t_flow = self._stage_refine(mb, x, flow_keys)
                    finish(k0 + k, mb, x, t_draft, t_flow)
            else:
                with ThreadPoolExecutor(max_workers=1) as pool:
                    fut = pool.submit(stage_draft, batches[0])
                    for k, mb in enumerate(batches):
                        x, flow_keys, t_draft = fut.result()
                        if k + 1 < len(batches):
                            fut = pool.submit(stage_draft, batches[k + 1])
                        x, t_flow = self._stage_refine(mb, x, flow_keys)
                        finish(k0 + k, mb, x, t_draft, t_flow)
            pending, fallback = fallback, []

        # speculatively accepted requests terminate HERE: the pre-pass
        # drafts (sliced to the request's own seq_len) are the result,
        # zero refine steps, never packed (micro_batch == -1)
        for acc in accepted:
            req = acc["request"]
            results[req.request_id] = RequestResult(
                request_id=req.request_id,
                tokens=np.asarray(acc["tokens"])[:, :req.seq_len],
                nfe=0, t0=acc["t0"],
                bucket_len=bucket_seq_len(req.seq_len,
                                          min_bucket=self.min_bucket,
                                          max_bucket=self.max_bucket),
                micro_batch=-1)

        batches = all_batches
        wall = time.perf_counter() - wall0
        overlapped = max(0.0, draft_total + flow_total - wall)
        denom = min(draft_total, flow_total)
        rows = sum(mb.rows for mb in batches)

        def req_mean_nfe(r: RequestResult) -> float:
            # per-row adaptive mode: a request's NFE spend is the mean
            # over its rows' own step counts (r.nfe stays the worst-row
            # bound); accepted requests spent 0
            if r.row_t0s:
                return float(np.mean([
                    guarantees.warm_nfe(self.cold_nfe, t) for t in r.row_t0s]))
            return float(r.nfe)

        nfe_values = [req_mean_nfe(r) for r in results.values()]
        report = {
            "num_requests": len(requests),
            "num_micro_batches": len(batches),
            "rows": rows,
            "padded_rows": sum(mb.padded_rows for mb in batches),
            "draft_time_s": draft_total,
            "flow_time_s": flow_total,
            "wall_time_s": wall,
            "overlap": self.overlap,
            "overlap_efficiency": (overlapped / denom) if denom > 0 else 0.0,
            "requests_per_s": len(requests) / wall if wall > 0 else float("inf"),
            "samples_per_s": rows / wall if wall > 0 else float("inf"),
            "mean_request_nfe": (float(np.mean(nfe_values))
                                 if nfe_values else 0.0),
            # this run's counts; lifetime totals live on the instance
            "jit_cache": self._jit_cache_delta(cache_snap),
            "mesh": None if self.mesh is None else dict(self.mesh.shape),
            "adaptive_t0": self.t0_policy is not None,
            "policy": policy_report,
            "speculative": (None if not self.speculative else {
                "enabled": True,
                "eligible": policy_report["speculative"]["eligible"],
                "accepted": len(accepted),
                "accept_rate": (
                    len(accepted) / policy_report["speculative"]["eligible"]
                    if policy_report["speculative"]["eligible"] else 0.0),
                "accept_score": self.accept_score,
                # worst probe score that shipped unrefined — must sit at
                # or above accept_score (benches gate on this)
                "min_accepted_score": (
                    min(float(np.min(a["scores"])) for a in accepted)
                    if accepted else None),
            }),
            "bandit": (self.t0_policy.arm_stats()
                       if self._bandit_mode else None),
            "distilled": (None if self.distilled_model is None else {
                "enabled": True,
                "nfe": self.distilled_nfe,
                "gate_score": self.distilled_accept_score,
                **distill_stats,
            }),
            "batches": batch_reports,
        }
        self._row_scores.clear()
        return results, report

    # ---- streaming / SLO-aware admission ---------------------------------

    def _t0_lower_bound(self, req: ServeRequest) -> float:
        """Shallowest t0 this request could be served at — the
        conservative bound the deadline estimator prices refine work at
        before the actual t0 is known (scored only at flush time)."""
        if req.t0 is not None:
            return float(req.t0)
        if self.t0_policy is not None:
            cal = getattr(self.t0_policy, "calibration", None)
            floor = getattr(cal, "t0_floor", None)
            if floor is not None:
                # the policy snaps the calibrated t0 DOWN onto its bin
                # grid, which can land up to one bin_width below the
                # calibration floor — back off a full bin so this stays
                # a true lower bound on the served t0
                width = float(getattr(self.t0_policy, "bin_width", 0.0))
                pfloor = float(getattr(self.t0_policy, "t0_floor", 0.0))
                return max(0.0, pfloor, float(floor) - width)
            return 0.0
        return self.default_t0

    def _stream_est_latency_s(self, fb: FillingBucket, unit: int,
                              backlog_s: float) -> float:
        """Estimated time from 'flush now' to 'results out' for a
        filling bucket: pipeline backlog + draft-stage EWMA + measured
        per-NFE refine cost x worst-case steps (compile surcharge when
        the compile key is novel). Zero until the first measurement —
        the admission loop then flushes on the raw deadline."""
        if fb.requests and fb.requests[0].tier == DISTILLED_TIER:
            # tier-homogeneous buckets (the filling key includes the
            # tier): a distilled bucket runs exactly K head steps
            n_steps = self.distilled_nfe
            key = (fb.bucket_len, pad_rows(fb.rows, unit), n_steps,
                   DISTILLED_TIER)
        else:
            t0_lb = min(self._t0_lower_bound(r) for r in fb.requests)
            n_steps = guarantees.warm_nfe(self.cold_nfe, t0_lb)
            key = (fb.bucket_len, pad_rows(fb.rows, unit), n_steps)
        est = self.cost_model.estimate_s(key, n_steps, include_compile=True)
        return backlog_s + (self._draft_cost_ewma or 0.0) + (est or 0.0)

    def _mb_est_latency_s(self, mb: MicroBatch) -> float:
        est = self.cost_model.estimate_s(
            mb.compile_key, mb.n_steps, include_compile=True)
        return (self._draft_cost_ewma or 0.0) + (est or 0.0)

    def _score_chunks_t0(self, chunks: Sequence[ServeRequest]) -> float:
        """Admission-time t0 for an oversize request under the adaptive
        policy: draft + score the request's rows CHUNK BY CHUNK (each
        dispatch stays within the micro-batch row cap and reuses the
        pipeline's compiled shapes — never one oversized draft batch)
        and take the min across all rows, so every chunk inherits the
        same request-level min-over-rows t0 the batch path's pre-pass
        would have chosen."""
        t0_min = 1.0
        for chunk in chunks:
            blen = bucket_seq_len(chunk.seq_len, min_bucket=self.min_bucket,
                                  max_bucket=self.max_bucket)
            seeds = np.full((chunk.num_samples,), chunk.seed, np.int32)
            idx = np.arange(chunk.sample_offset,
                            chunk.sample_offset + chunk.num_samples,
                            dtype=np.int32)
            draft_keys, _ = _derive_row_keys(jnp.asarray(seeds),
                                             jnp.asarray(idx))
            x = np.asarray(
                jax.block_until_ready(self.draft_fn(draft_keys, blen)))
            t0_min = min(t0_min, float(self.t0_policy.t0_for_drafts(x).min()))
        return t0_min

    def _flush_bucket(self, fb: FillingBucket, reason: str, now: float,
                      stats: dict) -> List[dict]:
        """FillingBucket -> dispatched micro-batches (state machine edge
        to DISPATCHED). Under the adaptive policy, the t0 scoring
        pre-pass runs HERE, per flushed bucket — requests without a t0
        override are drafted+scored in one batch and the drafts reused
        by the pipeline, exactly as the batch path's global pre-pass
        does per bucket. The whole edge is the ``flush`` span."""
        with self.tracer.span("flush", track="flush",
                              profile=_profile_name("flush"), reason=reason,
                              bucket=fb.bucket_len):
            occupancy = fb.rows
            self.tracer.instant("bucket_flush", track="flush", reason=reason,
                                bucket=fb.bucket_len, rows=occupancy,
                                requests=len(fb.requests))
            self.metrics.counter("serve.flush", reason=reason).inc()
            self.metrics.histogram(
                "bucket.flush_rows", buckets=(1, 2, 4, 8, 16, 32, 64, 128),
                bucket=fb.bucket_len).observe(occupancy)
            reqs = fb.flush()               # deadline order
            predrafted = None
            if self.t0_policy is not None:
                reqs, predrafted, prep, accepted = self._policy_prepass(reqs)
                stats["prepass_time_s"] += prep["prepass_time_s"]
                # speculatively accepted requests skip packing entirely; the
                # serving loop yields them as ACCEPTED_DRAFT terminals
                for acc in accepted:
                    acc["reason"] = reason
                    acc["flushed_s"] = now
                stats["accepted_pending"].extend(accepted)
            batches = pack_requests(
                reqs, cold_nfe=self.cold_nfe, default_t0=self.default_t0,
                max_rows=self.max_rows, min_bucket=self.min_bucket,
                max_bucket=self.max_bucket, row_quantum=self.row_quantum,
                row_multiple=self._row_multiple,
                t0_bin_width=self.t0_bin_width,
                distilled_nfe=self.distilled_nfe)
            for mb in batches:
                for span in mb.spans:
                    self.tracer.instant(
                        "request_packed", track="flush",
                        flow_id=span.request.root_id, flow_ph="t",
                        request_id=span.request.root_id, bucket=mb.bucket_len,
                        reason=reason)
            return [{"mb": mb, "predrafted": predrafted, "reason": reason,
                     "flushed_s": now} for mb in batches]

    def serve_stream(
        self,
        requests: Optional[Sequence[ServeRequest]] = None,
        *,
        source: Optional[AdmissionQueue] = None,
        slo_ms: Optional[float] = None,
        idle_timeout_s: float = 0.05,
        poll_interval_s: float = 0.002,
        clock=None,
    ) -> Iterator[CompletedRequest]:
        """Streaming, continuously-admitting serve loop.

        Yields a :class:`CompletedRequest` per request AS ITS MICRO-BATCH
        FINISHES (oversize requests are split across micro-batches and
        reassembled before yielding), instead of returning everything at
        end-of-run. Tokens are bit-identical to
        :meth:`serve_requests` for the same request set: per-row PRNG
        streams, bucket choice and NFE schedules are functions of the
        request alone, and the same per-row guarantee gates run on every
        dispatch.

        Admission: ``requests`` (admitted immediately) and/or ``source``
        (an :class:`AdmissionQueue` producers keep filling while serving
        is in flight). Requests accumulate in per-bucket
        :class:`~repro.serving.batcher.FillingBucket` accumulators and
        are dispatched when a bucket fills, when the oldest request's
        SLO budget would otherwise be blown (``slo_ms``; the estimated
        dispatch latency comes from the measured per-NFE cost model),
        when arrivals go quiet (``idle_timeout_s``), or when the source
        closes. The draft stage of the next micro-batch overlaps the
        refine of the current one, as in the batch path.

        Overload hardening: every admitted request resolves to exactly
        one terminal :class:`CompletedRequest` — ``COMPLETED`` with
        tokens, or ``CANCELLED`` / ``TIMED_OUT`` / ``SHED`` / ``FAILED``
        with an empty token array (never a silent drop). Cancelled and
        timed-out requests free their rows from the filling buckets (or
        are masked out of an already-packed micro-batch) without
        touching sibling rows' PRNG streams; requests shed by a bounded
        :class:`AdmissionQueue` surface with ``SHED``; a refine dispatch
        that still fails after :class:`DispatchRetryPolicy`'s backoff
        budget fails only its own micro-batch's requests with
        ``FAILED`` while the stream keeps serving. Priority classes get
        their own filling buckets, premium micro-batches dispatch ahead
        of best_effort ones, and per-class deadlines are scaled by
        ``class_slo_factor`` (best_effort has no deadline by default).

        After the generator is exhausted, ``self.stream_report`` holds
        the run's latency percentiles, SLO attainment (global and
        per-class), flush-reason counts, admission/shed/terminal-status
        ledgers with the conservation check, dispatch retry/failure
        counts and per-micro-batch stage timings.

        ``clock`` is an object with ``time()``/``sleep(dt)`` (defaults
        to monotonic wall time; tests inject a fake to drive deadlines).
        """
        clock = clock if clock is not None else _MonotonicClock()
        slo_s = None if slo_ms is None else float(slo_ms) / 1e3
        unit = math.lcm(self.row_quantum, self._row_multiple)
        if requests is None and source is None:
            raise ValueError("serve_stream needs `requests` and/or `source`")
        own_source = source is None
        if own_source:
            source = AdmissionQueue(clock=clock, metrics=self.metrics)
        if requests is not None:
            now0 = clock.time()
            with source._lock:
                for req in requests:
                    # arrival = stream start for pre-known request sets.
                    # Preloaded requests are counted in the admission
                    # ledger and get cancel tokens registered, so
                    # conservation accounting and source.cancel() hold
                    # for them too (the depth bound applies only to
                    # producer-side submissions — this set is already
                    # admitted by construction).
                    if req.arrival_s == 0.0:
                        req = dataclasses.replace(req, arrival_s=now0)
                    if req.cancel_token is None:
                        req = dataclasses.replace(
                            req, cancel_token=CancelToken())
                    source._tokens[req.request_id] = req.cancel_token
                    source._c_offered.inc()
                    source._c_accepted.inc()
                    source._items.append(req)
                    source._next_id = max(source._next_id,
                                          req.request_id + 1)
                source._g_depth.set(len(source._items))
        if own_source:
            # no external producer: the pre-known set IS the stream
            source.close()

        # filling buckets are keyed by (bucket_len, priority, tier): a
        # class never waits on (or pads into) another class's bucket, and
        # distilled traffic never perturbs a guaranteed bucket's flush
        # timing (or vice versa) — every micro-batch is pure-class and
        # pure-tier
        filling: Dict[Tuple[int, str, str], FillingBucket] = {}
        ready: List[dict] = []          # flushed micro-batches -> pipeline
        partials: Dict[int, dict] = {}  # parent_id -> chunk reassembly
        stats = {"prepass_time_s": 0.0, "accepted_pending": []}
        mb_reports: List[dict] = []
        latencies: List[float] = []
        class_latencies: Dict[str, List[float]] = {
            c: [] for c in PRIORITY_CLASSES}
        spec_min_score: Optional[float] = None
        distill_min_score: Optional[float] = None
        # as-admitted distilled requests, pre-resolution: a quality-floor
        # fallback re-enters the guaranteed path from THIS object, so its
        # re-pack (t0 scoring, PRNG streams, bucket choice) is
        # indistinguishable from a fresh guaranteed submission
        originals: Dict[int, ServeRequest] = {}
        draft_total = flow_total = 0.0
        t_first: Optional[float] = None
        first_arrival_s: Optional[float] = None
        # ONE registry snapshot anchors every report section: terminal
        # statuses, per-class SLO, flush reasons, jit cache, dispatch
        # retries — the stream report is DERIVED from counter deltas
        # against it, never from parallel hand-rolled dicts
        m0 = self._jit_cache_snapshot()
        wall0 = clock.time()
        # micro-batch ids, taken when a micro-batch is popped for drafting
        # so that all of its spans carry the id its requests will carry
        mb_index = itertools.count()
        # the open `wait` span of an unbroken stretch of polling with
        # nothing to dispatch (one span per stretch, not per sleep)
        waiting = None
        # terminal-status bookkeeping: every admitted ROOT request id
        # lands in `resolved` exactly once, with exactly one terminal
        # CompletedRequest yielded for it (conservation is checked in
        # the stream report); the status counts live in the registry
        # (`serve.terminal{priority,status}`)
        resolved: set = set()
        m = self.metrics
        tracer = self.tracer

        def count_terminal(status: str, priority: str) -> None:
            m.counter("serve.terminal", status=status, priority=priority).inc()

        def end_wait() -> None:
            """Close the idle stretch: the loop found work, or is about to
            yield (time spent in the caller is no program span's)."""
            nonlocal waiting
            if waiting is not None:
                waiting.__exit__(None, None, None)
                waiting = None

        def class_deadline(req: ServeRequest) -> Optional[float]:
            """arrival + slo * class factor, or None for classes whose
            factor is None (best_effort by default: it never forces a
            deadline flush and is excluded from SLO attainment)."""
            if slo_s is None:
                return None
            factor = self.class_slo_factor.get(req.priority, 1.0)
            if factor is None:
                return None
            return req.arrival_s + slo_s * factor

        def terminal(req: ServeRequest, status: str,
                     now: float) -> Optional[CompletedRequest]:
            """Resolve ``req``'s ROOT request to a non-COMPLETED terminal
            status; None when already resolved (oversize chunks share
            their parent's fate — one terminal event per root)."""
            root = req.root_id
            if root in resolved:
                return None
            end_wait()          # the caller yields the result
            resolved.add(root)
            originals.pop(root, None)
            part = partials.pop(root, None)
            n_chunks = part["num_chunks"] if part is not None else 1
            count_terminal(status, req.priority)
            # shed / timed-out / failed requests count AGAINST their
            # class's SLO attainment (the system failed to serve them in
            # time); a caller's cancel does not. `served=False` keeps
            # them out of the GLOBAL attainment (served results only).
            if status != CANCELLED and class_deadline(req) is not None:
                m.counter("serve.slo_total", priority=req.priority,
                          served=False).inc()
            tracer.instant("request_terminal", track="terminal",
                           flow_id=root, flow_ph="f", request_id=root,
                           status=status, priority=req.priority,
                           latency_ms=(now - req.arrival_s) * 1e3)
            return CompletedRequest(
                request_id=root,
                tokens=np.zeros((0, req.seq_len), np.int32),
                nfe=0, t0=0.0, bucket_len=0, micro_batch=-1,
                arrival_s=req.arrival_s, finished_s=now,
                latency_s=now - req.arrival_s, flush_reason="",
                deadline_s=None, slo_met=None, chunks=n_chunks,
                status=status, priority=req.priority)

        def admit(req: ServeRequest, now: float, *, fallback: bool = False):
            nonlocal first_arrival_s
            if req.parent_id is not None:
                # chunk metadata is minted by THIS loop's splitter; an
                # externally-fabricated chunk has no reassembly slot
                raise ValueError(
                    f"request {req.request_id} carries chunk metadata "
                    f"(parent_id={req.parent_id}); submit the parent "
                    f"request whole — the admission loop splits it")
            if not fallback:
                # a quality-floor fallback was already admitted once:
                # conservation sees one offer and exactly one terminal
                m.counter("serve.admitted").inc()
            if req.tier == DISTILLED_TIER:
                if self._distill_loop is None:
                    raise ValueError(
                        "tier='distilled' request admitted but the "
                        "scheduler has no distilled model")
                if req.num_samples > usable_rows(self.max_rows, unit):
                    # oversize requests split into chunks that must share
                    # one terminal fate; a per-chunk quality gate could
                    # strand a parent half-distilled, so oversize
                    # distilled requests serve on the guaranteed path
                    self._c_distill_downgrades.inc()
                    req = dataclasses.replace(req, tier=GUARANTEED_TIER)
                else:
                    originals[req.request_id] = req
            if first_arrival_s is None or req.arrival_s < first_arrival_s:
                first_arrival_s = req.arrival_s
            pieces = [req]
            if req.num_samples > usable_rows(self.max_rows, unit):
                pieces = split_request(
                    req, max_rows=self.max_rows, unit=unit,
                    alloc_id=lambda: next(self._chunk_ids))
                if self.t0_policy is not None and req.t0 is None:
                    t0 = self._score_chunks_t0(pieces)
                    pieces = [dataclasses.replace(p, t0=t0) for p in pieces]
                m.counter("serve.split_requests").inc()
                partials[req.request_id] = {
                    "tokens": None, "rows_done": 0, "chunks_done": 0,
                    "num_chunks": len(pieces), "arrival_s": req.arrival_s,
                    "seq_len": req.seq_len, "samples": req.num_samples,
                }
            for piece in pieces:
                blen = bucket_seq_len(piece.seq_len,
                                      min_bucket=self.min_bucket,
                                      max_bucket=self.max_bucket)
                fkey = (blen, piece.priority, piece.tier)
                fb = filling.get(fkey)
                if fb is not None and fb.would_overflow(
                        piece.num_samples, max_rows=self.max_rows,
                        unit=unit):
                    end_wait()
                    ready.extend(self._flush_bucket(fb, "full", now, stats))
                    fb = None
                if fb is None:
                    fb = FillingBucket(blen)
                    filling[fkey] = fb
                fb.add(piece, deadline_s=class_deadline(piece))

        def pop_ready() -> Optional[dict]:
            """Next micro-batch for the pipeline: best priority class
            first (FIFO within a class), skipping — and counting as
            dropped — micro-batches whose every span already resolved
            (cancelled / timed out while queued: no compute spent)."""
            while ready:
                best = min(
                    range(len(ready)),
                    key=lambda i: (
                        min(priority_rank(s.request.priority)
                            for s in ready[i]["mb"].spans), i))
                pending = ready.pop(best)
                if all(s.request.root_id in resolved
                       for s in pending["mb"].spans):
                    m.counter("serve.dropped_micro_batches").inc()
                    continue
                pending["k"] = next(mb_index)
                return pending
            return None

        def complete(pending: dict, x, t_draft: float, t_flow: float):
            """Turn one finished micro-batch into CompletedRequests.

            Spans whose request was cancelled or timed out in flight are
            masked out here: their computed rows are discarded and a
            CANCELLED/TIMED_OUT terminal result is emitted instead.
            Sibling rows are untouched — row PRNG streams, the bucket
            shape and the NFE schedule are functions of each request
            alone, so the surviving rows' bytes are identical either
            way."""
            nonlocal draft_total, flow_total, t_first, distill_min_score
            draft_total += t_draft
            flow_total += t_flow
            mb, k = pending["mb"], pending["k"]
            # quality floor for distilled micro-batches, BEFORE the clock
            # reads: the probe eval is part of serving the micro-batch
            gate = (self._distill_gate(mb, x)
                    if mb.tier == DISTILLED_TIER else None)
            finished_s = clock.time()
            # queue wait: from each packed request's arrival to the start
            # of its micro-batch's refine dispatch
            for span in mb.spans:
                m.histogram("serve.queue_wait_s").observe(
                    pending["dispatch_s"] - span.request.arrival_s)
            mb_reports.append({
                "micro_batch": k, "bucket_len": mb.bucket_len,
                "rows": mb.rows, "padded_rows": mb.padded_rows,
                "t0": mb.t0, "t0_spans": list(mb.t0_spans),
                "nfe": mb.n_steps, "tier": mb.tier,
                "flush_reason": pending["reason"],
                # the stream clock's times of its flush, the start of its
                # refine dispatch, and its completion
                "flushed_s": pending["flushed_s"],
                "dispatch_s": pending["dispatch_s"], "done_s": finished_s,
                "draft_time_s": t_draft, "flow_time_s": t_flow,
            })
            x_host = np.asarray(x)
            out = []
            for span, span_t0, span_rows in zip(mb.spans, mb.t0_spans,
                                                mb.row_t0_spans):
                req = span.request
                if req.root_id in resolved:
                    continue    # already terminal (a sibling chunk's fate)
                if req.cancelled:
                    item = terminal(req, CANCELLED, finished_s)
                    if item is not None:
                        out.append(item)
                    continue
                if req.expired(finished_s):
                    item = terminal(req, TIMED_OUT, finished_s)
                    if item is not None:
                        out.append(item)
                    continue
                status, nfe = COMPLETED, guarantees.warm_nfe(
                    self.cold_nfe, span_t0)
                if gate is not None:
                    # distilled requests are never chunked (oversize ones
                    # were downgraded at admission), so the gate decides
                    # the whole request right here
                    passed, mn = gate[req.request_id]
                    if not passed:
                        # quality floor missed: fall back to the
                        # guaranteed path. Re-admission starts from the
                        # AS-ADMITTED request (t0 unresolved, untouched
                        # DRAFT/FLOW streams), so the re-pack is
                        # bit-identical to a fresh guaranteed request —
                        # and serve.admitted is NOT recounted, keeping
                        # conservation at one offer, one terminal.
                        self._c_distill_fallbacks.inc()
                        tracer.instant(
                            "request_fallback", track="flush",
                            flow_id=req.root_id, flow_ph="t",
                            request_id=req.root_id, score=mn,
                            gate_score=self.distilled_accept_score)
                        admit(dataclasses.replace(
                            originals.pop(req.request_id),
                            tier=GUARANTEED_TIER), finished_s,
                            fallback=True)
                        continue
                    originals.pop(req.request_id, None)
                    distill_min_score = (mn if distill_min_score is None
                                         else min(distill_min_score, mn))
                    status, nfe = DISTILLED, self.distilled_nfe
                toks = x_host[span.row_offset:span.row_offset + span.rows,
                              :req.seq_len]
                if req.parent_id is not None:
                    part = partials[req.parent_id]
                    if part["tokens"] is None:
                        part["tokens"] = np.zeros(
                            (part["samples"], part["seq_len"]), toks.dtype)
                    part["tokens"][req.sample_offset:
                                   req.sample_offset + req.num_samples] = toks
                    part["rows_done"] += req.num_samples
                    part["chunks_done"] += 1
                    if part["rows_done"] < part["samples"]:
                        continue
                    rid, tokens = req.parent_id, part["tokens"]
                    arrival, chunks = part["arrival_s"], part["num_chunks"]
                    del partials[req.parent_id]
                else:
                    rid, tokens = req.request_id, toks
                    arrival, chunks = req.arrival_s, 1
                resolved.add(rid)
                deadline = class_deadline(req)
                met = None if deadline is None else finished_s <= deadline
                latency = finished_s - arrival
                latencies.append(latency)
                class_latencies[req.priority].append(latency)
                count_terminal(status, req.priority)
                m.histogram("serve.latency_s",
                            priority=req.priority).observe(latency)
                if deadline is not None:
                    m.counter("serve.slo_total", priority=req.priority,
                              served=True).inc()
                    if met:
                        m.counter("serve.slo_met",
                                  priority=req.priority).inc()
                tracer.instant("request_terminal", track="terminal",
                               flow_id=rid, flow_ph="f", request_id=rid,
                               status=status, priority=req.priority,
                               latency_ms=latency * 1e3)
                if t_first is None:
                    t_first = finished_s
                out.append(CompletedRequest(
                    request_id=rid, tokens=tokens, nfe=nfe,
                    t0=span_t0, bucket_len=mb.bucket_len, micro_batch=k,
                    row_t0s=(span_rows if chunks == 1 and status != DISTILLED
                             else ()),
                    arrival_s=arrival, finished_s=finished_s,
                    latency_s=latency, flush_reason=pending["reason"],
                    deadline_s=deadline, slo_met=met, chunks=chunks,
                    status=status, priority=req.priority))
            return out

        draft_fut = None
        draft_pending = None
        # retry backoff inside _dispatch_refine must sleep on THIS
        # stream's clock (tests drive a fake one)
        self._stream_clock = clock
        try:
            with ThreadPoolExecutor(max_workers=1) as pool:
                while True:
                    now = clock.time()
                    # overload: requests the bounded queue evicted become
                    # SHED terminal results, never silent drops. Every
                    # request the loop first sees (shed or drained) opens
                    # its flow chain with a request_admitted instant, so
                    # admission→terminal trace coverage equals the
                    # conservation ledger exactly.
                    for req in source.take_shed():
                        tracer.instant("request_admitted", track="admission",
                                       flow_id=req.root_id, flow_ph="s",
                                       request_id=req.root_id,
                                       priority=req.priority,
                                       seq_len=req.seq_len)
                        item = terminal(req, SHED, now)
                        if item is not None:
                            yield item
                    for req in source.drain():
                        tracer.instant("request_admitted", track="admission",
                                       flow_id=req.root_id, flow_ph="s",
                                       request_id=req.root_id,
                                       priority=req.priority,
                                       seq_len=req.seq_len)
                        if req.cancelled:
                            item = terminal(req, CANCELLED, now)
                            if item is not None:
                                yield item
                            continue
                        if req.expired(now):
                            item = terminal(req, TIMED_OUT, now)
                            if item is not None:
                                yield item
                            continue
                        admit(req, now)
                    source_done = source.closed
                    # cancellation / timeout sweep: pruned requests free
                    # their rows BEFORE packing, so siblings bucket and
                    # pack exactly as if the pruned request never arrived
                    for fkey in list(filling):
                        fb = filling[fkey]
                        for req, status in fb.prune(now):
                            item = terminal(req, status, now)
                            if item is not None:
                                yield item
                        if not fb.requests:
                            del filling[fkey]
                    # deadline / idle / drain flush sweep
                    backlog_s = sum(self._mb_est_latency_s(p["mb"])
                                    for p in ready)
                    if draft_pending is not None:
                        backlog_s += self._mb_est_latency_s(
                            draft_pending["mb"])
                    for fkey in list(filling):
                        fb = filling[fkey]
                        if not fb.requests:
                            del filling[fkey]
                            continue
                        reason = ("drain" if source_done
                                  else fb.flush_decision(
                                      now,
                                      est_latency_s=self._stream_est_latency_s(
                                          fb, unit, backlog_s),
                                      idle_timeout_s=idle_timeout_s,
                                      max_rows=self.max_rows, unit=unit))
                        if reason:
                            end_wait()
                            ready.extend(
                                self._flush_bucket(fb, reason, now, stats))
                            del filling[fkey]
                    # speculative accepts terminate here: the pre-pass
                    # drafts ship as ACCEPTED_DRAFT terminals with zero
                    # refine steps — rejected siblings already re-packed
                    # above, bit-identical to speculation-off serving
                    while stats["accepted_pending"]:
                        acc = stats["accepted_pending"].pop(0)
                        req = acc["request"]
                        now_a = clock.time()
                        if req.root_id in resolved:
                            continue
                        if req.cancelled:
                            item = terminal(req, CANCELLED, now_a)
                            if item is not None:
                                yield item
                            continue
                        if req.expired(now_a):
                            item = terminal(req, TIMED_OUT, now_a)
                            if item is not None:
                                yield item
                            continue
                        resolved.add(req.request_id)
                        s_min = float(np.min(acc["scores"]))
                        spec_min_score = (
                            s_min if spec_min_score is None
                            else min(spec_min_score, s_min))
                        deadline = class_deadline(req)
                        met = None if deadline is None else now_a <= deadline
                        latency = now_a - req.arrival_s
                        latencies.append(latency)
                        class_latencies[req.priority].append(latency)
                        count_terminal(ACCEPTED_DRAFT, req.priority)
                        m.histogram("serve.latency_s",
                                    priority=req.priority).observe(latency)
                        if deadline is not None:
                            m.counter("serve.slo_total",
                                      priority=req.priority,
                                      served=True).inc()
                            if met:
                                m.counter("serve.slo_met",
                                          priority=req.priority).inc()
                        tracer.instant("request_terminal", track="terminal",
                                       flow_id=req.request_id, flow_ph="f",
                                       request_id=req.request_id,
                                       status=ACCEPTED_DRAFT,
                                       priority=req.priority,
                                       latency_ms=latency * 1e3)
                        if t_first is None:
                            t_first = now_a
                        end_wait()
                        yield CompletedRequest(
                            request_id=req.request_id,
                            tokens=np.asarray(acc["tokens"])[:, :req.seq_len],
                            nfe=0, t0=acc["t0"],
                            bucket_len=bucket_seq_len(
                                req.seq_len, min_bucket=self.min_bucket,
                                max_bucket=self.max_bucket),
                            micro_batch=-1,
                            arrival_s=req.arrival_s, finished_s=now_a,
                            latency_s=latency, flush_reason=acc["reason"],
                            deadline_s=deadline, slo_met=met, chunks=1,
                            status=ACCEPTED_DRAFT, priority=req.priority)
                    # pipeline: draft of the NEXT micro-batch overlaps the
                    # refine of the current one (same structure as the
                    # batch path's worker thread)
                    if draft_fut is None and ready:
                        draft_pending = pop_ready()
                        if draft_pending is not None:
                            draft_fut = pool.submit(
                                self._stage_keys_and_draft,
                                draft_pending["mb"],
                                draft_pending["predrafted"],
                                draft_pending["k"])
                    if draft_fut is not None:
                        end_wait()
                        k = draft_pending["k"]
                        with tracer.span(
                                "draft_wait", track="refine_dispatch",
                                profile=_profile_name("draft_wait", k),
                                micro_batch=k):
                            x, flow_keys, t_draft = draft_fut.result()
                        current, draft_fut, draft_pending = \
                            draft_pending, None, None
                        if ready:
                            draft_pending = pop_ready()
                            if draft_pending is not None:
                                draft_fut = pool.submit(
                                    self._stage_keys_and_draft,
                                    draft_pending["mb"],
                                    draft_pending["predrafted"],
                                    draft_pending["k"])
                        current["dispatch_s"] = clock.time()
                        try:
                            x, t_flow = self._stage_refine(
                                current["mb"], x, flow_keys, k)
                        except DispatchFailure:
                            # fault isolation: the retry budget is spent —
                            # fail ONLY this micro-batch's requests and
                            # keep serving the stream
                            m.counter("serve.failed_micro_batches").inc()
                            draft_total += t_draft
                            fail_s = clock.time()
                            for span in current["mb"].spans:
                                item = terminal(span.request, FAILED, fail_s)
                                if item is not None:
                                    yield item
                            continue
                        with tracer.span("complete", track="refine_dispatch",
                                         profile=_profile_name("complete", k),
                                         micro_batch=k):
                            items = complete(current, x, t_draft, t_flow)
                        for item in items:
                            yield item
                        continue
                    if source_done and not filling and not ready \
                            and draft_fut is None:
                        break
                    if waiting is None:
                        waiting = tracer.span("wait", track="admission",
                                              profile=_profile_name("wait"))
                        waiting.__enter__()
                    clock.sleep(poll_interval_s)
        finally:
            end_wait()
            self._stream_clock = None

        wall = clock.time() - wall0

        def pct(vals, q):
            return float(np.percentile(vals, q)) if vals else 0.0

        # ---- report assembly: every counter-valued section below is a
        # registry delta against the m0 snapshot — the registry is the
        # single source of truth (raw latency lists stay local only for
        # exact percentiles)
        parsed = [(parse_metric_key(k), v)
                  for k, v in self.metrics.counter_deltas(m0).items()]

        def dsum(name: str, **match) -> int:
            want = {k: str(v) for k, v in match.items()}
            return sum(v for (n, labels), v in parsed
                       if n == name and all(labels.get(mk) == mv
                                            for mk, mv in want.items()))

        admission = source.stats()
        statuses = (COMPLETED, ACCEPTED_DRAFT, DISTILLED, CANCELLED,
                    TIMED_OUT, SHED, FAILED)
        terminal_counts = {s: dsum("serve.terminal", status=s)
                           for s in statuses}
        completed_n = terminal_counts[COMPLETED]
        resolved_total = sum(terminal_counts.values())
        flush_reasons = {labels["reason"]: v for (n, labels), v in parsed
                         if n == "serve.flush"}
        scored_requests = dsum("policy.scored_requests")
        slo_served = dsum("serve.slo_total", served=True)
        slo_met_n = dsum("serve.slo_met")
        by_class_report = {}
        for cname in PRIORITY_CLASSES:
            counts = {s: dsum("serve.terminal", status=s, priority=cname)
                      for s in statuses}
            if not any(counts.values()):
                continue
            lat = class_latencies[cname]
            ctot = dsum("serve.slo_total", priority=cname)
            cmet = dsum("serve.slo_met", priority=cname)
            by_class_report[cname] = {
                "completed": counts[COMPLETED],
                "accepted_draft": counts[ACCEPTED_DRAFT],
                "distilled": counts[DISTILLED],
                "shed": counts[SHED],
                "cancelled": counts[CANCELLED],
                "timed_out": counts[TIMED_OUT],
                "failed": counts[FAILED],
                "slo_attainment": (cmet / ctot if ctot else None),
                "latency_ms": {
                    "p50": pct(lat, 50) * 1e3, "p95": pct(lat, 95) * 1e3,
                    "p99": pct(lat, 99) * 1e3, "n": len(lat),
                },
            }
        self.stream_report = {
            "streaming": True,
            "num_requests": dsum("serve.admitted"),
            "completed": completed_n,
            "accepted_draft": terminal_counts[ACCEPTED_DRAFT],
            "distilled_served": terminal_counts[DISTILLED],
            "num_micro_batches": len(mb_reports),
            "split_requests": dsum("serve.split_requests"),
            "flush_reasons": dict(sorted(flush_reasons.items())),
            "slo_ms": slo_ms,
            "slo_attainment": (slo_met_n / slo_served
                               if slo_served else None),
            "latency_s": {
                "mean": float(np.mean(latencies)) if latencies else 0.0,
                "p50": pct(latencies, 50), "p95": pct(latencies, 95),
                "p99": pct(latencies, 99),
                "max": float(np.max(latencies)) if latencies else 0.0,
            },
            # clock starts at the FIRST ADMISSION, not at generator start:
            # an open-loop stream may idle before traffic begins, and that
            # wait is not the engine's latency
            "time_to_first_result_s": (
                None if t_first is None
                else t_first - (first_arrival_s
                                if first_arrival_s is not None else wall0)),
            "wall_time_s": wall,
            "draft_time_s": draft_total,
            "flow_time_s": flow_total,
            "jit_cache": self._jit_cache_delta(m0),
            "adaptive_t0": self.t0_policy is not None,
            "policy": (None if self.t0_policy is None else
                       {"scored_requests": scored_requests,
                        "prepass_time_s": stats["prepass_time_s"]}),
            "speculative": (None if not self.speculative else {
                "enabled": True,
                "accepted": terminal_counts[ACCEPTED_DRAFT],
                "eligible": scored_requests,
                "accept_rate": (
                    terminal_counts[ACCEPTED_DRAFT] / scored_requests
                    if scored_requests else 0.0),
                "accept_score": self.accept_score,
                "min_accepted_score": spec_min_score,
            }),
            "bandit": (self.t0_policy.arm_stats()
                       if self._bandit_mode else None),
            "distilled": (None if self.distilled_model is None else {
                "enabled": True,
                "nfe": self.distilled_nfe,
                "gate_score": self.distilled_accept_score,
                "served": terminal_counts[DISTILLED],
                "fallbacks": dsum("distilled.fallbacks"),
                "gate_evals": dsum("distilled.gate_evals"),
                "oversize_downgrades": dsum("distilled.oversize_downgrades"),
                # worst probe score that shipped distilled — must sit at
                # or above gate_score (benches gate on this)
                "min_served_score": distill_min_score,
            }),
            # overload-hardening sections: the admission ledger, terminal
            # status counts, per-class outcomes/latency and the exact
            # conservation check (offered == rejected + every terminal)
            "admission": admission,
            "terminal": dict(terminal_counts),
            "by_class": by_class_report,
            "conservation": {
                "offered": admission["offered"],
                "rejected": admission["rejected"],
                "resolved": resolved_total,
                "balanced": (admission["offered"]
                             == admission["rejected"] + resolved_total),
            },
            "dropped_micro_batches": dsum("serve.dropped_micro_batches"),
            "dispatch": {
                "retries": dsum("dispatch.retries"),
                "failed_micro_batches": dsum("serve.failed_micro_batches"),
                "failed_requests": terminal_counts[FAILED],
                "max_retries": self.retry_policy.max_retries,
                "backoff_base_s": self.retry_policy.backoff_base_s,
            },
            "batches": mb_reports,
        }
        self._row_scores.clear()


def _histogram(values: List[float]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for v in values:
        k = f"{v:.3f}"
        out[k] = out.get(k, 0) + 1
    return out
