"""Bring-up smoke of the warm-start serving path on a TPU.

Run from the root of a checkout, in one process that owns the chip:

    python chip_smoke.py              # serve + kernel phases on one chip
    python chip_smoke.py --chips 4    # the scheduler's 4-device mesh path
                                      # against the same requests on one chip

Phases (one chip):

* ``serve_fixed`` / ``serve_adaptive``: DFM-DiT at its published widths
  (``configs/dfm_dit.py::CONFIG``: 12 layers, width 768, 12 heads, float32),
  parameters from ``model.init`` with ``--seed``, the LSTM
  ``ARDraftEngine`` draft stage, served by ``WarmStartScheduler`` at
  ``cold_nfe=32`` with a fixed ``t0=0.8`` and then with the adaptive t0
  policy and speculative accept. Every request must end ``COMPLETED`` (or
  ``ACCEPTED_DRAFT`` under speculation) with no dispatch retry or failure,
  at exactly ``warm_nfe(cold_nfe, t0)`` refine steps, with tokens in
  ``[0, V)``. A second serve of the same requests, and the batch path
  (``serve_requests``), must give the same bits as the stream. Each
  refine key's trace must have taken the attention the ``"auto"`` rule
  gives its bucket: the fused kernel on the chip.
* ``backbone_logits``: one micro-batch's backbone logits on the chip
  against a float32 forward on the host CPU at "highest" matmul precision.
* ``kernels``: every Pallas kernel compiled for the chip (the lowered HLO
  holds ``tpu_custom_call``) and checked against its reference; flash
  attention as the refine calls it, at DFM-DiT's and StarCoder2-3B's head
  widths (grouped KV heads).

Each phase prints its wall seconds, its compile seconds and compile count,
and the device's peak bytes in use so far. These are bring-up facts, not
benchmark numbers. The last line of output is the device as JAX reports
it; it is printed only when every phase passed, and never without a TPU.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.dfm_dit import CONFIG, tiny_config  # noqa: E402
from repro.core import guarantees  # noqa: E402
from repro.core.paths import WarmStartPath  # noqa: E402
from repro.core.sampler import euler_step_probs  # noqa: E402
from repro.data import SyntheticCorpus  # noqa: E402
from repro.drafting import (  # noqa: E402
    AdaptiveT0Policy, ARDraftEngine, LSTMDraftAdapter, fit_t0_calibration,
    make_quality_scorer,
)
from repro.drafting.quality import DEFAULT_TIERS  # noqa: E402
from repro.kernels import DraftDecoder, resolve_interpret  # noqa: E402
from repro.kernels.flash_attn import flash_attention_ref  # noqa: E402
from repro.kernels.ws_fused import ws_fused_steps  # noqa: E402
from repro.kernels.ws_step import (  # noqa: E402
    seed_from_key, threefry_gumbel, ws_step, ws_step_ref_streamed,
)
from repro.models import LSTMConfig, LSTMModel, build_model  # noqa: E402
from repro.models.attention import (  # noqa: E402
    attention_impl, fused_attention,
)
from repro.serving import (  # noqa: E402
    ACCEPTED_DRAFT, COMPLETED, ServeRequest, WarmStartScheduler,
)

COLD_NFE = 32
FIXED_T0 = 0.8
# (bucket, requests, samples per request): two full 32-row micro-batches
# per bucket
TRAFFIC = ((256, 16, 4), (1024, 8, 8))
MAX_ROWS = 32

# The chip's default float32 matmul rounds both inputs to bf16 (8
# significant bits: at most 2**-9 relative error per rounding) and
# accumulates in float32. A DFM-DiT layer holds 6 matmuls in series (qkv,
# scores, attention-weighted values, output, MLP up, MLP down), and the
# head adds one; independent roundings add in quadrature, so a relative
# L2 error near sqrt(6 * layers + 1) * 2**-9 is expected. The bound is
# twice that: 0.033 at 12 layers.
def logits_rel_l2_bound(num_layers: int) -> float:
    return 2.0 * math.sqrt(6 * num_layers + 1) * 2.0 ** -9


# flash attention on inputs of order 1: one bf16 rounding of each matmul
# input (2**-9) on scores of order 1, with a 2x margin
FLASH_TOL = 2.0 ** -8
# empirical next-token distribution vs euler_step_probs: the expected total
# variation of n samples over V categories is about 0.5 * sqrt(V / n) *
# sqrt(2 / pi); the bound is three times 0.5 * sqrt(V / n). Noise repeated
# across row blocks would cut the effective n by the block size and break it.
def tv_bound(vocab: int, n: int) -> float:
    return 1.5 * math.sqrt(vocab / n)


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def rel_l2(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


# ---------------------------------------------------------------------------
# per-phase accounting
# ---------------------------------------------------------------------------

class CompileMeter:
    """Seconds of backend compiles and their number (jit-cache misses),
    from ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        # tracing and lowering nest for inner jits; the backend compile
        # does not, and it is most of the time on a TPU
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


@contextlib.contextmanager
def phase(name: str, report: dict):
    """Time one phase and print its bring-up line."""
    t0 = time.perf_counter()
    with CompileMeter() as meter:
        yield report
    report.update(
        phase=name, wall_s=time.perf_counter() - t0,
        compile_s=meter.seconds, compiles=meter.compiles,
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    print("phase " + json.dumps(report, default=float), flush=True)


# ---------------------------------------------------------------------------
# the served stack
# ---------------------------------------------------------------------------

def build_stack(cfg, seed: int, max_bucket: int):
    """Backbone at ``cfg`` with random weights from ``seed``, and the LSTM
    AR draft engine of ``launch/serve.py`` (random weights too)."""
    model = build_model(cfg)
    params = model.init(jax.random.key(seed))
    lstm = LSTMModel(LSTMConfig(vocab_size=cfg.vocab_size, hidden=128,
                                num_layers=1, embed_dim=64))
    lparams = lstm.init(jax.random.key(seed + 1))
    engine = ARDraftEngine(LSTMDraftAdapter(model=lstm), lparams,
                           max_len=max_bucket)
    return model, params, engine


def make_requests(seed: int, traffic=TRAFFIC):
    """Requests of random length in ``(bucket / 2, bucket]``."""
    rng = np.random.default_rng(seed)
    reqs = []
    for bucket, count, samples in traffic:
        for _ in range(count):
            reqs.append(ServeRequest(
                request_id=len(reqs),
                seq_len=int(rng.integers(bucket // 2 + 1, bucket + 1)),
                num_samples=samples, seed=1000 * seed + len(reqs)))
    return reqs


def check_results(results, reqs, vocab: int, *, cold_nfe: int,
                  speculative: bool) -> dict:
    """Terminal status, NFE guarantee and token range of every request."""
    require(set(results) == {r.request_id for r in reqs},
            "every request gets exactly one result")
    allowed = {COMPLETED, ACCEPTED_DRAFT} if speculative else {COMPLETED}
    counts = {}
    for r in reqs:
        res = results[r.request_id]
        # batch-path results carry no status: an accepted draft is the
        # one that never entered a micro-batch
        status = getattr(res, "status",
                         ACCEPTED_DRAFT if res.micro_batch == -1 else COMPLETED)
        counts[status] = counts.get(status, 0) + 1
        require(status in allowed,
                f"request {r.request_id} ended {status!r}")
        want_nfe = (0 if status == ACCEPTED_DRAFT
                    else guarantees.warm_nfe(cold_nfe, res.t0))
        require(res.nfe == want_nfe,
                f"request {r.request_id}: nfe {res.nfe} != {want_nfe}")
        tok = np.asarray(res.tokens)
        require(tok.shape == (r.num_samples, r.seq_len),
                f"request {r.request_id}: tokens {tok.shape}")
        require(tok.min() >= 0 and tok.max() < vocab,
                f"request {r.request_id}: tokens outside [0, {vocab})")
    return counts


def same_bits(a, b, what: str) -> None:
    for rid in a:
        require(np.array_equal(np.asarray(a[rid].tokens),
                               np.asarray(b[rid].tokens))
                and a[rid].nfe == b[rid].nfe,
                f"{what}: request {rid} differs")


def require_clean_dispatch(sched) -> None:
    m = sched.metrics
    retries = m.counter("dispatch.retries").value
    failures = m.counter("dispatch.failures").value
    require(retries == 0 and failures == 0,
            f"dispatch retries {retries}, failures {failures}")


def serve_checked(sched, reqs, vocab: int, *, speculative: bool) -> dict:
    """Stream the requests twice and serve them once through the batch
    path: all three must give the same bits, and pass ``check_results``."""
    first = {c.request_id: c for c in sched.serve_stream(reqs)}
    counts = check_results(first, reqs, vocab, cold_nfe=sched.cold_nfe,
                           speculative=speculative)
    require(sched.stream_report["conservation"]["balanced"],
            "stream conservation ledger unbalanced")
    again = {c.request_id: c for c in sched.serve_stream(reqs)}
    same_bits(first, again, "second serve_stream")
    batch, _ = sched.serve_requests(reqs)
    check_results(batch, reqs, vocab, cold_nfe=sched.cold_nfe,
                  speculative=speculative)
    same_bits(first, batch, "serve_requests vs serve_stream")
    require_clean_dispatch(sched)
    return {"statuses": counts,
            "micro_batches": sched.stream_report["num_micro_batches"]}


def require_attention(sched, cfg) -> dict:
    """Each refine key's trace took the attention the ``"auto"`` rule
    gives its bucket (on the chip: the fused kernel from 128 tokens)."""
    taken = {}
    for key, entry in sched.stream_report["jit_cache"]["per_key"].items():
        bucket = int(key.strip("()").split(",")[0])
        want = attention_impl(cfg, mode="bidir", cached=False, window=None,
                              seq=bucket)
        require(entry.get("attention") == want,
                f"refine key {key}: attention {entry.get('attention')!r}, "
                f"not {want!r}")
        taken[key] = want
    return taken


def serve_phase(cfg, *, seed: int, traffic=TRAFFIC,
                max_rows: int = MAX_ROWS) -> None:
    """The main path at ``cfg``: fixed t0, then adaptive t0 with
    speculative accept, then one micro-batch's logits against the CPU
    (its first rows: a full-width float32 forward is slow on the host)."""
    ref_rows = 4
    max_bucket = max(b for b, _, _ in traffic)
    min_bucket = min(b for b, _, _ in traffic)
    model, params, engine = build_stack(cfg, seed, max_bucket)
    reqs = make_requests(seed, traffic)
    vocab = cfg.vocab_size
    kw = dict(flow_model=model, flow_params=params,
              draft_fn=engine.as_draft_fn(), cold_nfe=COLD_NFE,
              max_rows=max_rows, min_bucket=min_bucket, max_bucket=max_bucket)

    with phase("serve_fixed", {"t0": FIXED_T0, "requests": len(reqs)}) as rep:
        sched = WarmStartScheduler(**kw, default_t0=FIXED_T0)
        rep.update(serve_checked(sched, reqs, vocab, speculative=False))
        rep["attention"] = require_attention(sched, cfg)

    with phase("serve_adaptive", {"t0": "auto", "speculative": True,
                                  "requests": len(reqs)}) as rep:
        scorer = make_quality_scorer(model.dfm_apply, params)
        data = SyntheticCorpus(seed=seed).sequences(256, min_bucket, seed=1)
        calib = fit_t0_calibration(scorer, data, vocab, seed=seed)
        policy = AdaptiveT0Policy(scorer=scorer, calibration=calib)
        sched = WarmStartScheduler(
            **kw, default_t0=min(t0 for _, t0 in DEFAULT_TIERS),
            t0_policy=policy, speculative=True)
        rep.update(serve_checked(sched, reqs, vocab, speculative=True))
        rep["calibration_scores"] = list(calib.scores)

    with phase("backbone_logits", {"rows": max_rows, "bucket": min_bucket,
                                   "ref_rows": ref_rows}) as rep:
        keys = jax.random.split(jax.random.key(seed + 2), max_rows)
        x = engine.generate_rows(keys, min_bucket)
        t = jnp.full((max_rows,), FIXED_T0, jnp.float32)
        got = np.asarray(jax.jit(model.dfm_apply)(params, x, t))[:ref_rows]
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu), \
                jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(model.dfm_apply)(
                jax.device_put(params, cpu),
                jax.device_put(x[:ref_rows], cpu),
                jax.device_put(t[:ref_rows], cpu)))
        err, bound = rel_l2(got, ref), logits_rel_l2_bound(cfg.num_layers)
        rep.update(rel_l2=err, bound=bound,
                   max_abs=float(np.abs(got - ref).max()))
        require(np.isfinite(got).all(), "non-finite backbone logits")
        require(err <= bound,
                f"backbone logits rel L2 {err:.3g} > bound {bound:.3g}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def lowered_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text()


def require_kernel(fn, args, interpret: bool, what: str) -> None:
    """A compiled kernel's lowered HLO holds the Mosaic custom call."""
    if not interpret:
        require("tpu_custom_call" in lowered_text(fn, *args),
                f"{what}: no tpu_custom_call in the lowered HLO")


def check_ws_step(seed: int, rows: int, vocab: int, interpret: bool) -> None:
    path = WarmStartPath(t0=0.0)
    ks = jax.random.split(jax.random.key(seed), 4)
    logits = jax.random.normal(ks[0], (rows, vocab)) * 3
    x = jax.random.randint(ks[1], (rows,), 0, vocab)
    t = jax.random.uniform(ks[2], (rows,), maxval=0.95)
    h = jnp.asarray(1.0 / COLD_NFE)

    def step(rng, logits, x, t):
        return ws_step(rng, logits, x, t, h, path, hw_prng=False,
                       interpret=interpret)

    require_kernel(step, (ks[3], logits, x, t), interpret,
                   f"ws_step threefry V={vocab}")
    out = jax.jit(step)(ks[3], logits, x, t)
    a = jnp.clip(h * path.velocity_scale(t), 0.0, 1.0)
    g = threefry_gumbel(seed_from_key(ks[3]), rows, vocab)
    ref = ws_step_ref_streamed(logits, x, a, g)
    require(np.array_equal(np.asarray(out), np.asarray(ref)),
            f"ws_step threefry V={vocab}: differs from ws_step_ref_streamed")


def check_ws_step_hw_prng(seed: int, rows: int, vocab: int, seeds: int,
                          interpret: bool) -> dict:
    """Hardware-PRNG draws: valid tokens whose empirical distribution
    matches ``euler_step_probs`` within ``tv_bound``."""
    path = WarmStartPath(t0=0.0)
    row = jax.random.normal(jax.random.key(seed), (1, vocab)) * 2
    logits = jnp.broadcast_to(row, (rows, vocab))
    x = jnp.full((rows,), 3, jnp.int32)
    t = jnp.full((rows,), 0.5)
    h = jnp.asarray(0.25)                      # a = h / (1 - t) = 0.5

    def step(rng):
        return ws_step(rng, logits, x, t, h, path, hw_prng=True,
                       interpret=interpret)

    require_kernel(step, (jax.random.key(0),), interpret,
                   f"ws_step hw-PRNG V={vocab}")
    draws = np.concatenate([
        np.asarray(jax.jit(step)(k))
        for k in jax.random.split(jax.random.key(seed + 1), seeds)])
    require(draws.min() >= 0 and draws.max() < vocab,
            f"ws_step hw-PRNG V={vocab}: tokens outside [0, V)")
    probs = np.asarray(euler_step_probs(row, x[:1], t[:1], h, path))[0]
    hist = np.bincount(draws, minlength=vocab) / draws.size
    tv = 0.5 * float(np.abs(hist - probs).sum())
    bound = tv_bound(vocab, draws.size)
    require(tv <= bound, f"ws_step hw-PRNG V={vocab}: TV {tv:.4f} > {bound:.4f}")
    return {"tv": tv, "tv_bound": bound, "samples": int(draws.size)}


def check_ws_fused(seed: int, rows: int, vocab: int, interpret: bool,
                   hw_prng: bool, k: int = 4) -> None:
    """K fused steps == K composed ws_step calls, bit for bit (threefry);
    the hardware-PRNG megakernel compiles and draws valid tokens."""
    path = WarmStartPath(t0=FIXED_T0)
    ks = jax.random.split(jax.random.key(seed), 3)
    logits = jax.random.normal(ks[0], (rows, vocab))
    x = jax.random.randint(ks[1], (rows,), 0, vocab)
    h = 1.0 / COLD_NFE
    ts = jnp.asarray([FIXED_T0 + i * h for i in range(k)], jnp.float32)
    hs = jnp.full((k,), h, jnp.float32)
    keys = jax.random.split(ks[2], k)

    def fused(keys, logits, x, hw):
        return ws_fused_steps(keys, logits, x, ts, hs, path, impl="fused",
                              hw_prng=hw, interpret=interpret)

    def composed(keys, logits, x):
        for j in range(k):
            x = ws_step(keys[j], logits, x, ts[j], hs[j], path,
                        hw_prng=False, interpret=interpret)
        return x

    require_kernel(lambda *a: fused(*a, False), (keys, logits, x),
                   interpret, f"ws_fused threefry V={vocab}")
    out = jax.jit(lambda *a: fused(*a, False))(keys, logits, x)
    ref = jax.jit(composed)(keys, logits, x)
    require(np.array_equal(np.asarray(out), np.asarray(ref)),
            f"ws_fused K={k} V={vocab}: differs from composed ws_step")
    if hw_prng:
        require_kernel(lambda *a: fused(*a, True), (keys, logits, x),
                       interpret, f"ws_fused hw-PRNG V={vocab}")
        hw_out = np.asarray(jax.jit(lambda *a: fused(*a, True))(
            keys, logits, x))
        require(hw_out.min() >= 0 and hw_out.max() < vocab,
                f"ws_fused hw-PRNG V={vocab}: tokens outside [0, V)")


# query heads, KV heads, head width: DFM-DiT's and StarCoder2-3B's
FLASH_HEADS = ((CONFIG.num_heads, CONFIG.num_kv_heads, CONFIG.head_dim),
               (24, 2, 128))


def check_flash_attn(seed: int, seq: int, interpret: bool,
                     heads=FLASH_HEADS) -> dict:
    """The attention the refine takes, as the model calls it: the
    ``"auto"`` rule picks the fused kernel for a bidirectional bucket of
    ``seq`` on the chip, and ``fused_attention`` (KV heads read through
    the index map) matches a float32 reference at "highest" precision."""
    if not interpret:
        impl = attention_impl(CONFIG, mode="bidir", cached=False,
                              window=None, seq=seq)
        require(impl == "fused", f"attn_impl auto takes {impl!r} at {seq}")
    rep = {}
    for h, kh, d in heads:
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (1, seq, h, d))
        k, v = (jax.random.normal(kk, (1, seq, kh, d)) for kk in ks[1:])
        scale = 1.0 / math.sqrt(d)

        def fa(q, k, v):
            return fused_attention(q, k, v, scale, interpret)

        what = f"flash_attn bidirectional {h}/{kh}x{d}"
        require_kernel(fa, (q, k, v), interpret, what)
        out = np.asarray(jax.jit(fa)(q, k, v))
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(
                lambda q, k, v: flash_attention_ref(
                    q, jnp.repeat(k, h // kh, 2), jnp.repeat(v, h // kh, 2),
                    causal=False))(q, k, v))
        err = float(np.abs(out - ref).max())
        require(np.allclose(out, ref, atol=FLASH_TOL, rtol=FLASH_TOL),
                f"{what}: max abs error {err:.3g} beyond {FLASH_TOL}")
        rep[f"{h}/{kh}x{d}"] = {"max_abs": err, "tol": FLASH_TOL}
    return rep


def check_draft_decode(seed: int, interpret: bool, *, batch: int = 4,
                       seq: int = 16, max_len: int = 64) -> dict:
    """Batched prefill == scan prefill, bit for bit (logits and cache),
    and the kernel forward tracks a float32 CPU decode. The draft is a
    2-layer transformer at the tiny DFM-DiT widths (192, 6 heads of 32)."""
    cfg = tiny_config(vocab_size=CONFIG.vocab_size,
                      seq_len=max_len).replace(num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.key(seed))
    dec = DraftDecoder(model, interpret=interpret)
    toks = jax.random.randint(jax.random.key(seed + 1), (batch, seq), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    chunk = jax.jit(dec.forward_chunk)
    cache0 = model.init_cache(batch, max_len, jnp.float32)
    if not interpret:
        text = lowered_text(dec.forward_chunk, params, toks, cache0, 0)
        require(text.count("tpu_custom_call") >= 4,
                "draft_decode: fewer than 4 kernels in the lowered HLO")
    lg_b, cache_b = chunk(params, toks, cache0, 0)
    cache_s = model.init_cache(batch, max_len, jnp.float32)
    per_tok = []
    for i in range(seq):
        lg, cache_s = chunk(params, toks[:, i:i + 1], cache_s, i)
        per_tok.append(np.asarray(lg))
    lg_s = np.concatenate(per_tok, axis=1)
    require(np.array_equal(np.asarray(lg_b), lg_s),
            "draft_decode: batched prefill logits differ from scan prefill")
    for lb, ls in zip(jax.tree.leaves(cache_b), jax.tree.leaves(cache_s)):
        require(np.array_equal(np.asarray(lb), np.asarray(ls)),
                "draft_decode: batched prefill cache differs from scan")
    # reference: the model's own XLA decode, token by token, in float32 on
    # the host CPU
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        p_cpu, t_cpu = jax.device_put(params, cpu), jax.device_put(toks, cpu)
        cache = model.init_cache(batch, max_len, jnp.float32)
        step = jax.jit(model.decode_step)
        ref = []
        for i in range(seq):
            lg, cache = step(p_cpu, t_cpu[:, i:i + 1], cache, i)
            ref.append(np.asarray(lg))
    err, bound = rel_l2(lg_s, np.concatenate(ref, axis=1)), \
        logits_rel_l2_bound(cfg.num_layers)
    require(err <= bound,
            f"draft_decode: logits rel L2 {err:.3g} vs CPU > {bound:.3g}")
    return {"rel_l2_vs_cpu": err, "bound": bound}


def kernel_phase(*, seed: int, interpret: bool = False, hw_prng: bool = True,
                 ws_shapes=((8192, 27), (256, 32768)), flash_seq: int = 1024,
                 draft_shape=(4, 16, 64)) -> None:
    """``hw_prng=False`` leaves out the hardware-PRNG checks, which only a
    chip can run (the Pallas interpreter has no such generator)."""
    with phase("kernels", {"interpret": interpret}) as rep:
        for rows, vocab in ws_shapes:
            check_ws_step(seed, rows, vocab, interpret)
        if hw_prng:
            rep["ws_step_hw_prng"] = check_ws_step_hw_prng(
                seed, 8192, CONFIG.vocab_size, 8, interpret)
        for rows, vocab in ws_shapes:
            check_ws_fused(seed, rows, vocab, interpret, hw_prng)
        rep["flash_attn"] = check_flash_attn(seed, flash_seq, interpret)
        batch, seq, max_len = draft_shape
        rep["draft_decode"] = check_draft_decode(
            seed, interpret, batch=batch, seq=seq, max_len=max_len)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def mesh_phase(cfg, *, seed: int, traffic=((256, 8, 4), (1024, 4, 8)),
               max_rows: int = MAX_ROWS) -> None:
    """The scheduler's ``mesh=`` path (SERVE_RULES) over a 2x2 mesh of
    ``data`` x ``model``, against the same requests on one chip."""
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_local_mesh

    count = len(jax.devices())
    require(count == 4, f"the mesh phase needs 4 devices, found {count}")
    mesh = make_local_mesh(model_parallel=2)
    max_bucket = max(b for b, _, _ in traffic)
    min_bucket = min(b for b, _, _ in traffic)
    model, params, engine = build_stack(cfg, seed, max_bucket)
    reqs = make_requests(seed, traffic)
    kw = dict(flow_model=model, flow_params=params,
              draft_fn=engine.as_draft_fn(), cold_nfe=COLD_NFE,
              default_t0=FIXED_T0, max_rows=max_rows, min_bucket=min_bucket,
              max_bucket=max_bucket)

    with phase("serve_one_chip", {"requests": len(reqs)}) as rep:
        one = WarmStartScheduler(**kw)
        single = {c.request_id: c for c in one.serve_stream(reqs)}
        rep["statuses"] = check_results(single, reqs, cfg.vocab_size,
                                        cold_nfe=COLD_NFE, speculative=False)
        require_clean_dispatch(one)

    with phase("serve_mesh", {"mesh": dict(mesh.shape),
                              "requests": len(reqs)}) as rep:
        sched = WarmStartScheduler(**kw, mesh=mesh)
        param_shardings = shd.param_shardings(params, shd.SERVE_RULES, mesh)
        leaves = jax.tree_util.tree_flatten_with_path(sched.flow_params)[0]
        split = [leaf for _, leaf in leaves
                 if not leaf.sharding.is_fully_replicated]
        require(all(len(leaf.sharding.device_set) == 4 for _, leaf in leaves),
                "a parameter is not placed on all 4 devices")
        require(split, "no parameter is partitioned over the mesh")
        per_dev = {str(d.id): 0 for d in mesh.devices.flat}
        for _, leaf in leaves:
            for shard in leaf.addressable_shards:
                per_dev[str(shard.device.id)] += shard.data.nbytes
        total = sum(leaf.nbytes for _, leaf in leaves)
        specs = {jax.tree_util.keystr(p): str(leaf.sharding.spec)
                 for p, leaf in leaves
                 if cfg.vocab_size in leaf.shape}
        meshed = {c.request_id: c for c in sched.serve_stream(reqs)}
        rep["statuses"] = check_results(meshed, reqs, cfg.vocab_size,
                                        cold_nfe=COLD_NFE, speculative=False)
        require_clean_dispatch(sched)
        match = [np.asarray(meshed[r].tokens) == np.asarray(single[r].tokens)
                 for r in single]
        rep.update(
            param_bytes_total=total, param_bytes_per_device=per_dev,
            partitioned_leaves=len(split), leaves=len(leaves),
            vocab_dim_specs=specs,
            bytes_in_use_per_device={
                str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                for d in mesh.devices.flat},
            token_match_share=float(np.mean(np.concatenate(
                [m.ravel() for m in match]))))

    with phase("mesh_logits", {"rows": max_rows, "bucket": min_bucket}) as rep:
        keys = jax.random.split(jax.random.key(seed + 2), max_rows)
        x = engine.generate_rows(keys, min_bucket)
        t = jnp.full((max_rows,), FIXED_T0, jnp.float32)
        ref = np.asarray(jax.jit(model.dfm_apply)(params, x, t))

        def apply(p, x, t):
            with shd.axis_rules(shd.SERVE_RULES, mesh):
                return model.dfm_apply(p, x, t)

        rows1 = shd.batch_sharding(mesh, 1)
        rows2 = shd.batch_sharding(mesh, 2)
        got = np.asarray(jax.jit(
            apply, in_shardings=(param_shardings, rows2, rows1))(
            sched.flow_params, jax.device_put(x, rows2),
            jax.device_put(t, rows1)))
        err, bound = rel_l2(got, ref), logits_rel_l2_bound(cfg.num_layers)
        rep.update(rel_l2=err, bound=bound)
        require(err <= bound,
                f"mesh logits rel L2 {err:.3g} vs one chip > {bound:.3g}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, requests and inputs")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path and its one-chip "
                         "comparison, over 4 devices")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # the CPU-test default of the kernels must resolve to compiled mode here
    require(resolve_interpret(None) is False,
            "kernels would run in interpret mode on this backend")
    if args.chips == 4:
        mesh_phase(CONFIG, seed=args.seed)
    else:
        serve_phase(CONFIG, seed=args.seed)
        kernel_phase(seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
