"""Draft-quality scoring and score -> t0 calibration.

The paper's Fig. 4 ties the warm-start time to draft quality tiers
(pretty-good / fair / poor -> deep / medium / shallow t0). This module
makes that operational:

  * :func:`make_quality_scorer` — per-token likelihood probe of a draft
    under the LEARNED flow path: evaluate the backbone ``v_theta(x,
    t_probe)`` on the draft itself and read off the mean log-probability
    it assigns to *keeping* the draft tokens. Drafts near the data
    manifold score high; corrupted drafts score low. One backbone
    evaluation per scored batch — the probe costs exactly 1 NFE.
  * :func:`fit_t0_calibration` — offline fit of the monotone score -> t0
    mapping from the corruption tiers: corrupt held-out data at the
    paper's tier rates, score each tier with the probe, and anchor the
    tier's target t0 at its mean score. Serving interpolates between
    anchors (clipped to [t0_floor, t0_ceil]).
  * :func:`measure_cost_ratio` — measured (not assumed) draft cost:
    ``perf_counter`` timing of the draft stage against one backbone NFE,
    the ``draft_cost_ratio`` that :func:`repro.core.guarantees
    .speedup_report` charges against the speed-up.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.draft import CorruptionDraft

# paper Fig. 4 tiers: (corruption rate, target warm-start time)
DEFAULT_TIERS: Tuple[Tuple[float, float], ...] = (
    (0.05, 0.9),   # pretty good
    (0.30, 0.7),   # fair
    (0.60, 0.5),   # poor
)


def make_quality_scorer(
    apply_fn: Callable[[object, jax.Array, jax.Array], jax.Array],
    params,
    *,
    t_probe: float = 0.5,
    temperature: float = 1.0,
    probe_times: Optional[Sequence[float]] = None,
) -> Callable[[jax.Array], jax.Array]:
    """Build ``score(tokens (B, N)) -> (B,) mean per-token log-prob``.

    ``apply_fn(params, tokens, t (B,)) -> logits (B, N, V)`` is the
    backbone's ``dfm_apply`` signature. The probe asks the denoiser, at
    mid-path time ``t_probe``, how much mass its ``p1`` prediction keeps
    on the draft's own tokens — the learned analogue of "how close is
    this draft to the data".

    ``probe_times`` (2–3 values, e.g. ``(0.3, 0.5, 0.7)``) replaces the
    single ``t_probe`` with a MULTI-TIME probe: the score is the mean of
    the per-token log-prob over the probe times, one backbone evaluation
    per time. Near-manifold drafts look good at every path time while a
    single mid-path probe can be fooled by drafts that happen to sit
    close to one time's marginal — averaging sharpens the separation
    between the corruption tiers at a known, fixed extra cost
    (``len(probe_times)`` NFE per scored batch instead of 1). The single
    ``t_probe`` default is bit-identical to the pre-multi-time scorer.
    """
    times = tuple(float(t) for t in
                  (probe_times if probe_times is not None else (t_probe,)))
    if not times:
        raise ValueError("probe_times must name at least one probe time")
    if any(not (0.0 < t < 1.0) for t in times):
        raise ValueError(
            f"probe times must lie in (0, 1), got {times}")

    # the weights are an argument, not a closure: a closure would bake
    # them into every compiled program as constants
    @jax.jit
    def score(params, tokens: jax.Array) -> jax.Array:
        tokens = jnp.asarray(tokens, jnp.int32)

        def one_time(tp: float) -> jax.Array:
            t = jnp.full((tokens.shape[0],), tp, jnp.float32)
            logits = (apply_fn(params, tokens, t).astype(jnp.float32)
                      / temperature)
            logp = jax.nn.log_softmax(logits, axis=-1)
            tok_lp = jnp.take_along_axis(
                logp, tokens[..., None], axis=-1)[..., 0]
            return tok_lp.mean(axis=-1)

        total = one_time(times[0])
        for tp in times[1:]:
            total = total + one_time(tp)
        return total / len(times)

    return functools.partial(score, params)


@dataclasses.dataclass(frozen=True)
class T0Calibration:
    """Monotone piecewise-linear score -> t0 mapping.

    ``scores`` ascend; ``t0s`` are non-decreasing (higher likelihood ->
    deeper warm start). Outside the anchored range the mapping clamps to
    [t0_floor, t0_ceil] — an out-of-distribution *bad* draft can never be
    granted a deep t0, and a great one never exceeds the ceiling.
    """

    scores: Tuple[float, ...]
    t0s: Tuple[float, ...]
    t0_floor: float = 0.0
    t0_ceil: float = 0.95

    def __post_init__(self):
        if len(self.scores) != len(self.t0s) or len(self.scores) < 2:
            raise ValueError("need >= 2 (score, t0) anchors")
        if list(self.scores) != sorted(self.scores):
            raise ValueError("anchor scores must ascend")
        if not (0.0 <= self.t0_floor <= self.t0_ceil < 1.0):
            raise ValueError(
                f"need 0 <= t0_floor <= t0_ceil < 1, got "
                f"[{self.t0_floor}, {self.t0_ceil}]")

    def t0_for_scores(self, scores) -> np.ndarray:
        s = np.asarray(scores, np.float64)
        t0 = np.interp(s, np.asarray(self.scores), np.asarray(self.t0s))
        return np.clip(t0, self.t0_floor, self.t0_ceil)

    def t0_for_score(self, score: float) -> float:
        return float(self.t0_for_scores([score])[0])


def fit_t0_calibration(
    scorer: Callable[[jax.Array], jax.Array],
    data: np.ndarray,
    vocab_size: int,
    *,
    tiers: Sequence[Tuple[float, float]] = DEFAULT_TIERS,
    num_per_tier: int = 64,
    seed: int = 0,
    t0_floor: Optional[float] = None,
    t0_ceil: Optional[float] = None,
) -> T0Calibration:
    """Offline calibration from the corruption tiers (paper Fig. 4).

    For each (corruption_rate, target_t0) tier, corrupt ``num_per_tier``
    held-out rows at that rate, run the probe, and anchor ``target_t0``
    at the tier's mean score. Anchors are sorted by score and the t0
    sequence made monotone (cumulative min from the best tier down) so a
    noisy probe can never produce an inverted mapping.
    """
    anchors = []
    for i, (rate, target_t0) in enumerate(tiers):
        draft = CorruptionDraft(data=data, vocab_size=vocab_size,
                                corruption=rate)
        x = draft.generate(jax.random.key(seed + i), num_per_tier)
        s = float(np.asarray(scorer(x)).mean())
        anchors.append((s, float(target_t0)))
    anchors.sort(key=lambda a: a[0])
    scores = [float(a[0]) for a in anchors]
    # enforce monotone non-decreasing t0 along ascending score
    t0s = [float(v) for v in np.maximum.accumulate([a[1] for a in anchors])]
    floor = min(t0s) if t0_floor is None else t0_floor
    ceil = max(t0s) if t0_ceil is None else t0_ceil
    return T0Calibration(scores=tuple(scores), t0s=tuple(t0s),
                         t0_floor=floor, t0_ceil=ceil)


@dataclasses.dataclass(frozen=True)
class CostRatioReport:
    """Measured draft-vs-backbone timing (per generated batch)."""

    draft_time_s: float              # one draft-stage batch
    nfe_time_s: float                # one backbone evaluation + Euler step
    cost_ratio: float                # draft_time_s / nfe_time_s
    batch: int
    seq_len: int
    iters: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def _timed_best_of(fn, iters: int) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def measure_cost_ratio(
    draft_fn: Callable[[], jax.Array],
    nfe_fn: Callable[[], jax.Array],
    *,
    batch: int,
    seq_len: int,
    iters: int = 5,
    warmup: int = 1,
) -> CostRatioReport:
    """Measure ``draft_cost_ratio`` for :func:`guarantees.speedup_report`.

    ``draft_fn()`` must produce one draft batch, ``nfe_fn()`` one backbone
    function evaluation (+ Euler update) at the same (batch, seq_len).
    Both are warmed first (compile excluded), then timed best-of-``iters``
    with ``block_until_ready`` (wall time, the quantity the guarantee
    accounting charges).
    """
    for _ in range(warmup):
        jax.block_until_ready(draft_fn())
        jax.block_until_ready(nfe_fn())
    draft_s = _timed_best_of(draft_fn, iters)
    nfe_s = _timed_best_of(nfe_fn, iters)
    return CostRatioReport(
        draft_time_s=draft_s,
        nfe_time_s=nfe_s,
        cost_ratio=draft_s / max(nfe_s, 1e-12),
        batch=batch,
        seq_len=seq_len,
        iters=iters,
    )
