"""Euler CTMC sampling for (warm-start) discrete flow matching.

Implements the paper's Fig. 3: starting at ``t = t0`` from draft samples,
repeatedly form the probability update

    p1   = softmax(v_theta(x_t, t))
    u    = velocity_scale(t) * (p1 - onehot(x_t))        # generator
    x_t ~ Categorical( onehot(x_t) + h * u )

until ``t`` reaches 1. With ``t0 = 0`` and noise initialisation this is
exactly the cold-start DFM sampler of Gat et al. (2024); the warm-start
variant only changes the start time/state — hence the *guaranteed*
speed-up factor ``1/(1 - t0)`` in function evaluations.

The refine loop is a single jitted ``lax.scan`` over a precomputed
``(keys, t, h)`` schedule: the per-step times and (possibly partial
final) step sizes are computed host-side once, the PRNG key is split
once, and the whole loop compiles to ONE device dispatch — no host-side
``random.split`` per step and no per-step retrace. ``kernels/ws_step`` provides the fused Pallas step
(``step_fn``); this module also holds the pure-jnp per-step reference
used on CPU and as the oracle.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.paths import WarmStartPath


class SamplerStats(NamedTuple):
    nfe: jax.Array          # number of function evaluations actually taken
    final_t: jax.Array


def euler_step_probs(
    logits: jax.Array,
    x_t: jax.Array,
    t: jax.Array,
    h: jax.Array,
    path: WarmStartPath,
    *,
    temperature: float = 1.0,
) -> jax.Array:
    """Next-state categorical probabilities for one Euler step.

    p_next = onehot(x_t) + h * scale(t) * (p1 - onehot(x_t))
           = (1 - h*scale) * onehot(x_t) + h*scale * p1

    which is a convex combination whenever ``h * scale <= 1`` — we clip to
    guarantee a valid distribution at the final (possibly partial) step.
    """
    p1 = jax.nn.softmax(logits.astype(jnp.float32) / temperature, axis=-1)
    scale = path.velocity_scale(t)
    a = jnp.clip(h * scale, 0.0, 1.0)  # mixing weight toward p1
    a = jnp.expand_dims(a, axis=tuple(range(jnp.ndim(a), p1.ndim)))
    onehot = jax.nn.one_hot(x_t, logits.shape[-1], dtype=jnp.float32)
    return (1.0 - a) * onehot + a * p1


def categorical_from_probs(rng: jax.Array, probs: jax.Array) -> jax.Array:
    """Gumbel-max sampling from (possibly unnormalised) probabilities."""
    g = jax.random.gumbel(rng, probs.shape, dtype=jnp.float32)
    return jnp.argmax(jnp.log(jnp.maximum(probs, 1e-30)) + g, axis=-1).astype(jnp.int32)


def categorical_from_probs_rows(keys: jax.Array, probs: jax.Array) -> jax.Array:
    """Row-keyed Gumbel-max: ``keys (B,)`` typed PRNG keys, ``probs (B, ...)``.

    Row ``b``'s draw depends only on ``keys[b]`` — the noise for a request
    is a function of its own key, never of its neighbours or its position
    in the batch. This is what makes the continuous-batching scheduler's
    outputs independent of micro-batch composition.
    """
    g = jax.vmap(
        lambda k, p: jax.random.gumbel(k, p.shape, dtype=jnp.float32)
    )(keys, probs)
    return jnp.argmax(jnp.log(jnp.maximum(probs, 1e-30)) + g, axis=-1).astype(jnp.int32)


def make_euler_one_step_rows(path: "WarmStartPath", *, temperature: float = 1.0):
    """Row-keyed variant of :func:`make_euler_one_step`.

    ``one_step(keys (B,), logits, x_t, t (B,), h) -> x_next`` — same
    probability update, but the categorical draw is keyed per row so a
    request's trajectory is invariant to micro-batch packing. (The fused
    Pallas ``step_fn`` is single-key and is not supported here.)
    """

    def one_step(keys, logits, x_t, t, h):
        probs = euler_step_probs(logits, x_t, t, h, path, temperature=temperature)
        return categorical_from_probs_rows(keys, probs)

    return one_step


def refine_schedule(t0: float, cold_nfe_h: float, n: int):
    """Per-step ``(t, h)`` arrays for the warm-start Euler loop.

    ``t[i] = t0 + i * h`` and ``h[i] = min(h, 1 - t[i])`` so the last
    (possibly partial) step lands exactly on ``t = 1``. Computed on the
    host once, fed to the scanned loop as f32 arrays.
    """
    ts = (t0 + np.arange(n, dtype=np.float64) * cold_nfe_h).astype(np.float32)
    hs = np.minimum(np.float32(cold_nfe_h), np.float32(1.0) - ts).astype(np.float32)
    return ts, hs


def refine_schedule_rows(t0_rows, cold_nfe_h: float, cold_nfe: int):
    """Per-row schedule matrices for a heterogeneous-t0 micro-batch.

    Every row follows the SAME step size ``h = cold_nfe_h`` but enters the
    shared scan at its own step index: row ``r`` with warm-start time
    ``t0_rows[r]`` is inactive for the first ``n_max - n_r`` steps (where
    ``n_r = warm_nfe(cold_nfe, t0_rows[r])`` and ``n_max = max_r n_r``)
    and then takes exactly its guaranteed ``n_r`` Euler steps, so the
    batch's scan length realises the worst row's guarantee factor
    ``1/(1 - min t0)`` and no row ever exceeds its own ``warm_nfe``.

    Pack invariance: ``key_idx`` is each row's LOCAL step counter
    (0..n_r-1 on its active steps), so the PRNG fold sequence a row sees
    is independent of ``n_max`` — i.e. of which rows it was batched with.
    A batch whose rows all share one t0 reproduces
    :func:`refine_schedule` bit-exactly in every column.

    Returns ``(ts, hs, active, key_idx, nfe_rows)`` — the first four are
    ``(n_max, B)`` arrays (f32 / f32 / bool / int32), ``nfe_rows`` is the
    per-row guaranteed NFE ``(B,)`` with ``active.sum(0) == nfe_rows``.
    """
    from repro.core import guarantees

    t0_rows = np.asarray(t0_rows, np.float64)
    if t0_rows.ndim != 1:
        raise ValueError(f"t0_rows must be 1-D, got shape {t0_rows.shape}")
    nfe_rows = np.array(
        [guarantees.warm_nfe(cold_nfe, float(t)) for t in t0_rows], np.int32
    )
    n_max = int(nfe_rows.max())
    local = np.arange(n_max, dtype=np.int64)[:, None] - (n_max - nfe_rows)[None, :]
    active = local >= 0
    # same float path as refine_schedule: f64 accumulate, f32 cast, f32 h clip
    ts = (t0_rows[None, :] + np.where(active, local, 0) * cold_nfe_h).astype(np.float32)
    hs = np.where(
        active,
        np.minimum(np.float32(cold_nfe_h), np.float32(1.0) - ts),
        np.float32(0.0),
    ).astype(np.float32)
    key_idx = np.where(active, local, 0).astype(np.int32)
    return ts, hs, active, key_idx, nfe_rows


def distill_schedule_rows(t0_rows, num_steps: int):
    """Per-row K-step schedule for the DISTILLED few-step refiner tier.

    Where :func:`refine_schedule_rows` prices row ``r`` at its guaranteed
    ``warm_nfe(cold_nfe, t0_r)`` steps of the COLD step size, the
    distilled head collapses the whole ``[t0_r, 1]`` trajectory into
    exactly ``num_steps`` (K in {1, 2}) equal steps per row:
    ``h_r = (1 - t0_r) / K``, with the same final-step clip to land on
    ``t = 1``. Every row is active on every step and ``nfe_rows == K``
    for all rows regardless of the batch's t0 spread — the structural
    "NFE <= K" the distilled SLO tier is priced (and bench-gated) on.

    Returns ``(ts, hs, active, key_idx, nfe_rows)`` in the same shapes
    and dtypes as :func:`refine_schedule_rows`, so
    :func:`scan_refine_loop_rows` consumes either schedule unchanged.
    """
    if num_steps < 1:
        raise ValueError(f"num_steps must be >= 1, got {num_steps}")
    t0_rows = np.asarray(t0_rows, np.float64)
    if t0_rows.ndim != 1:
        raise ValueError(f"t0_rows must be 1-D, got shape {t0_rows.shape}")
    if np.any(t0_rows < 0.0) or np.any(t0_rows >= 1.0):
        raise ValueError(f"t0_rows must lie in [0, 1), got {t0_rows}")
    b = t0_rows.shape[0]
    h_rows = (1.0 - t0_rows) / num_steps
    local = np.arange(num_steps, dtype=np.int64)[:, None]
    # same float path as refine_schedule: f64 accumulate, f32 cast, clip h
    ts = (t0_rows[None, :] + local * h_rows[None, :]).astype(np.float32)
    hs = np.minimum(
        h_rows[None, :].astype(np.float32), np.float32(1.0) - ts
    ).astype(np.float32)
    active = np.ones((num_steps, b), dtype=bool)
    key_idx = np.broadcast_to(
        np.arange(num_steps, dtype=np.int32)[:, None], (num_steps, b)
    ).astype(np.int32)
    nfe_rows = np.full((b,), num_steps, np.int32)
    return ts, hs, active, key_idx, nfe_rows


def scan_refine_loop_rows(
    logits_fn: Callable[[jax.Array, jax.Array], jax.Array],
    one_step: Callable,
    x_init: jax.Array,
    flow_keys: jax.Array,
    ts: jax.Array,
    hs: jax.Array,
    active: jax.Array,
    key_idx: jax.Array,
    *,
    fused_block: int = 1,
    fused_fn: Optional[Callable] = None,
):
    """Masked per-row refine loop: ONE ``lax.scan`` serving rows whose t0
    (and therefore NFE) differ, each on its own slice of the shared
    schedule (see :func:`refine_schedule_rows`).

    Args:
      logits_fn: ``(tokens (B,N), t (B,)) -> logits (B,N,V)``.
      one_step: row-keyed step (see :func:`make_euler_one_step_rows`).
      x_init: (B, N) int32 draft state.
      flow_keys: (B,) typed per-row PRNG keys; step keys are
        ``fold_in(flow_keys[b], key_idx[i, b])`` so a row's noise stream
        is a function of its own key and local step counter only.
      ts / hs / active / key_idx: ``(n, B)`` schedule matrices.
      fused_block / fused_fn: with ``K > 1`` the scan runs over
        ceil(n/K) blocks of K sampling steps against one backbone
        evaluation each (see :func:`scan_refine_loop`); ``fused_fn``
        receives the block's per-(step, row) folded keys as a (K, B) key
        matrix. Per-row entry masks are preserved exactly: inactive steps
        carry ``h = 0``, which the megakernel freezes bit-exactly — a row
        entering mid-block stays untouched until its first active step.

    Rows are frozen (``x`` passes through unchanged) on steps where
    ``active`` is False; the backbone still evaluates the full batch each
    step — heterogeneity inside a micro-batch should therefore stay small
    (the batcher's t0-bins bound it).

    The body's operations carry the ``named_scope`` ``backbone``
    (``logits_fn``) or ``sample_step`` (step keys, ``one_step`` /
    ``fused_fn`` and the row freeze) in their op metadata, which a
    profile of the compiled program keeps.
    """
    if fused_block > 1:
        if fused_fn is None:
            raise ValueError("fused_block > 1 requires fused_fn "
                             "(see repro.kernels.make_ws_fused_fn)")
        n = ts.shape[0]
        k = min(fused_block, n)
        nb = -(-n // k)
        bts = _pad_blocks(ts, nb * k, n, 1.0).reshape((nb, k) + ts.shape[1:])
        bhs = _pad_blocks(hs, nb * k, n, 0.0).reshape((nb, k) + hs.shape[1:])
        bidx = _pad_blocks(key_idx, nb * k, n, 0).reshape(
            (nb, k) + key_idx.shape[1:])

        def fused_body(x, inp):
            bt, bh, bi = inp                              # (K, B) each
            with jax.named_scope("sample_step"):
                keys = jax.vmap(
                    lambda idx: jax.vmap(jax.random.fold_in)(flow_keys, idx)
                )(bi)                                     # (K, B) typed keys
            with jax.named_scope("backbone"):
                logits = logits_fn(x, bt[0])
            with jax.named_scope("sample_step"):
                return fused_fn(keys, logits, x, bt, bh), None

        x, _ = jax.lax.scan(fused_body, x_init, (bts, bhs, bidx))
        return x

    def body(x, inp):
        t, h, act, idx = inp
        with jax.named_scope("sample_step"):
            keys = jax.vmap(jax.random.fold_in)(flow_keys, idx)
        with jax.named_scope("backbone"):
            logits = logits_fn(x, t)
        with jax.named_scope("sample_step"):
            x_next = one_step(keys, logits, x, t, h)
            return jnp.where(act[:, None], x_next, x), None

    x, _ = jax.lax.scan(body, x_init, (ts, hs, active, key_idx))
    return x


def make_euler_one_step(
    path: WarmStartPath,
    *,
    temperature: float = 1.0,
    step_fn: Optional[Callable] = None,
):
    """The single Euler update ``(rng, logits, x_t, t, h) -> x_next``.

    This is THE per-step body shared by :class:`EulerSampler`,
    :func:`make_refine_step`, the serving engine and the scheduler —
    probability update + categorical draw, or the fused Pallas kernel
    when ``step_fn`` is given.
    """
    if step_fn is not None:
        return step_fn

    def one_step(rng, logits, x_t, t, h):
        probs = euler_step_probs(logits, x_t, t, h, path, temperature=temperature)
        return categorical_from_probs(rng, probs)

    return one_step


def refine_loop_inputs(rng: jax.Array, t0: float, h: float, n: int):
    """Device-ready ``(keys, ts, hs)`` scan inputs for an n-step refine.

    The ONE way every consumer builds the schedule: the key is split once
    host-side (one key per step, shared across the batch) and the (t, h)
    schedule comes from :func:`refine_schedule`.
    """
    ts, hs = refine_schedule(t0, h, n)
    keys = jax.random.split(rng, n)
    return keys, jnp.asarray(ts), jnp.asarray(hs)


def _pad_blocks(arr, n: int, nf: int, pad_value):
    """Pad a leading-``nf`` schedule array up to ``n`` steps (block tail)."""
    if n == nf:
        return arr
    pad = jnp.broadcast_to(jnp.asarray(pad_value, arr.dtype),
                           (n - nf,) + arr.shape[1:])
    return jnp.concatenate([arr, pad], axis=0)


def scan_refine_loop(
    logits_fn: Callable[[jax.Array, jax.Array], jax.Array],
    one_step: Callable,
    x_init: jax.Array,
    keys: jax.Array,
    ts: jax.Array,
    hs: jax.Array,
    *,
    argmax_final: bool = False,
    fused_block: int = 1,
    fused_fn: Optional[Callable] = None,
):
    """The whole refine loop as ONE ``lax.scan`` over ``(keys, t, h)``.

    Shared by ``EulerSampler.sample``, ``WarmStartServer`` and the
    continuous-batching scheduler — there is exactly one scan body in the
    codebase. ``keys`` may carry any trailing shape (a single key per
    step, or a per-row ``(B,)`` key batch per step for request-seeded
    serving); ``one_step`` must match.

    Args:
      logits_fn: ``(tokens (B,N), t (B,)) -> logits (B,N,V)``.
      one_step: ``(key, logits, x, t (B,), h) -> x_next`` (see
        :func:`make_euler_one_step`).
      x_init: (B, N) int32 start state at ``ts[0]``.
      keys / ts / hs: leading-``n`` scan inputs (see
        :func:`refine_loop_inputs`).
      argmax_final: replace the last stochastic step with argmax(p1).
      fused_block / fused_fn: with ``fused_block = K > 1`` the scan runs
        over ceil(n/K) *blocks*: each block evaluates the backbone ONCE
        (at the block's first step time) and hands K consecutive sampling
        steps to ``fused_fn(keys (K,...), logits, x, ts (K,), hs (K,))``
        — the ``kernels.ws_fused`` megakernel (see
        :func:`repro.kernels.make_ws_fused_fn`). The final partial block
        is padded with ``h = 0`` steps, which the kernel freezes
        bit-exactly. This trades per-step logits refresh for HBM traffic
        (and NFE: ceil(n/K) backbone evals instead of n) — an OPT-IN
        approximation; ``fused_block=1`` is the paper-faithful loop.
        ``argmax_final`` keeps its final step unfused on fresh logits.
    """
    b = x_init.shape[0]
    n = ts.shape[0]

    if fused_block > 1:
        if fused_fn is None:
            raise ValueError("fused_block > 1 requires fused_fn "
                             "(see repro.kernels.make_ws_fused_fn)")
        nf = n - 1 if argmax_final else n
        x = x_init
        if nf > 0:
            k = min(fused_block, nf)
            nb = -(-nf // k)
            # h=0 tail padding: frozen rows, any key/t — use the last ones
            bts = _pad_blocks(ts[:nf], nb * k, nf, 1.0).reshape(nb, k)
            bhs = _pad_blocks(hs[:nf], nb * k, nf, 0.0).reshape(nb, k)
            bkeys = jnp.concatenate(
                [keys[:nf]] + [keys[nf - 1:nf]] * (nb * k - nf), axis=0
            ).reshape((nb, k) + keys.shape[1:])

            def fused_body(x, inp):
                bk, bt, bh = inp
                tb = jnp.full((b,), bt[0], jnp.float32)
                logits = logits_fn(x, tb)
                return fused_fn(bk, logits, x, bt, bh), None

            x, _ = jax.lax.scan(fused_body, x, (bkeys, bts, bhs))
        if argmax_final:
            tb = jnp.full((b,), ts[n - 1], jnp.float32)
            x = jnp.argmax(logits_fn(x, tb), axis=-1).astype(jnp.int32)
        return x

    last = np.arange(n) == n - 1

    def body(x, inp):
        key, t, step, is_last = inp
        tb = jnp.full((b,), t, jnp.float32)
        logits = logits_fn(x, tb)
        x_next = one_step(key, logits, x, tb, step)
        if argmax_final:
            x_det = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            x_next = jnp.where(is_last, x_det, x_next)
        return x_next, None

    x, _ = jax.lax.scan(body, x_init, (keys, ts, hs, jnp.asarray(last)))
    return x


@dataclasses.dataclass(frozen=True)
class EulerSampler:
    """Fixed-step Euler CTMC sampler over ``t in [path.t0, 1]``.

    Attributes:
      path: probability path (carries t0).
      num_steps: total steps the *cold-start* sampler would take over
        [0, 1]; the warm-start sampler takes ``ceil(num_steps*(1-t0))`` of
        the same step size — this is the paper's guaranteed reduction.
      temperature: softmax temperature on v_theta.
      argmax_final: if True, the last step takes argmax(p1) instead of a
        stochastic step (common low-variance finisher; off by default to
        stay paper-faithful).
      step_fn: optional fused replacement for the probability update +
        categorical draw, signature (rng, logits, x_t, t, h) -> x_next
        (the Pallas kernel plugs in here).
      fused_block: K > 1 chunks the refine loop into fused K-step blocks
        (one backbone evaluation + one ``kernels.ws_fused`` megakernel
        dispatch per block); backbone evals drop to ceil(nfe/K). Opt-in
        approximation — 1 (default) is the paper-faithful per-step loop.
      jit: compile the whole refine loop into one dispatch (skipped
        automatically under an outer trace). ``x_init`` is NOT donated —
        callers may reuse it; the serving engine donates at its own
        boundary where the buffer is fresh per request.
    """

    path: WarmStartPath
    num_steps: int = 20
    temperature: float = 1.0
    argmax_final: bool = False
    step_fn: Optional[Callable] = None
    fused_block: int = 1
    jit: bool = True

    def __post_init__(self):
        # per-instance compile cache keyed by model_fn: entries (and the
        # closures/params they capture) die with the sampler instead of
        # accumulating in a process-global jit cache.
        object.__setattr__(self, "_jit_cache", {})

    @property
    def h(self) -> float:
        return 1.0 / self.num_steps

    @property
    def nfe(self) -> int:
        """Guaranteed function-evaluation count (see guarantees.py)."""
        return self.path.num_steps(self.h)

    @property
    def backbone_evals(self) -> int:
        """Backbone evaluations actually dispatched (<= nfe; fused blocks
        amortise one evaluation over ``fused_block`` sampling steps)."""
        if self.fused_block <= 1:
            return self.nfe
        nf = self.nfe - 1 if self.argmax_final else self.nfe
        evals = -(-nf // self.fused_block) if nf > 0 else 0
        return evals + (1 if self.argmax_final else 0)

    def _scan_loop(self, model_fn, rng, x_init):
        """The whole refine loop as one lax.scan over (keys, t, h)."""
        keys, ts, hs = refine_loop_inputs(rng, self.path.t0, self.h, self.nfe)
        one_step = make_euler_one_step(
            self.path, temperature=self.temperature, step_fn=self.step_fn
        )
        fused_fn = None
        if self.fused_block > 1:
            from repro.kernels import make_ws_fused_fn
            fused_fn = make_ws_fused_fn(
                self.path, temperature=self.temperature)
        return scan_refine_loop(
            model_fn, one_step, x_init, keys, ts, hs,
            argmax_final=self.argmax_final,
            fused_block=self.fused_block, fused_fn=fused_fn,
        )

    def sample(
        self,
        rng: jax.Array,
        model_fn: Callable[[jax.Array, jax.Array], jax.Array],
        x_init: jax.Array,
    ):
        """Run the sampler (one device dispatch when ``jit`` is on).

        Args:
          rng: PRNG key.
          model_fn: ``(tokens (B,N), t (B,)) -> logits (B,N,V)``.
          x_init: (B, N) int32 — draft samples at ``t = t0`` (warm start)
            or noise at ``t = 0`` (cold start).
        Returns:
          (x_final, SamplerStats)
        """
        # under an outer jit/grad trace, jax.jit inlines into that trace
        if not self.jit:
            x = self._scan_loop(model_fn, rng, x_init)
        else:
            fn = self._jit_cache.get(model_fn)
            if fn is None:
                fn = jax.jit(partial(self._scan_loop, model_fn))
                self._jit_cache[model_fn] = fn
            x = fn(rng, x_init)
        # nfe is a static property of the schedule — keep it a python int so
        # the guarantee check works under jit tracing. Fused blocks only
        # ever LOWER the count below the guaranteed bound.
        stats = SamplerStats(nfe=self.backbone_evals, final_t=1.0)
        return x, stats


def make_refine_step(
    apply_fn: Callable,
    path: WarmStartPath,
    *,
    temperature: float = 1.0,
    step_fn: Optional[Callable] = None,
):
    """A single jit-able DFM refine step for the serving engine.

    Returns ``f(params, rng, x_t (B,N), t (B,), h) -> x_next`` — the
    unit the `dfm_refine` serving path lowers for the dry-run.
    """

    one_step = make_euler_one_step(path, temperature=temperature, step_fn=step_fn)

    def refine_step(params, rng, x_t, t, h):
        logits = apply_fn(params, x_t, t)
        return one_step(rng, logits, x_t, t, h)

    return refine_step
