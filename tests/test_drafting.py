"""Drafting subsystem tests: KV-cached AR engine vs the full-recompute
oracle (bit-exact across prefill lengths, batch sizes and partial cache
reuse), row-keyed pack invariance, quality scoring + t0 calibration, and
measured cost-ratio accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dfm_dit import tiny_config
from repro.core.draft import ARDraft, CorruptionDraft
from repro.core.guarantees import speedup_report
from repro.drafting import (
    ARDraftEngine, LSTMDraftAdapter, T0Calibration, TransformerDraftAdapter,
    fit_t0_calibration, make_quality_scorer, measure_cost_ratio,
)
from repro.drafting.ref import oracle_generate_rows
from repro.models import build_model
from repro.models.lstm import LSTMConfig, LSTMModel

VOCAB = 13


@pytest.fixture(scope="module")
def tfm():
    cfg = tiny_config(vocab_size=VOCAB, seq_len=64).replace(
        num_layers=2, d_model=32, num_heads=2, num_kv_heads=2, d_ff=64)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return TransformerDraftAdapter(model=model), params


@pytest.fixture(scope="module")
def lstm():
    model = LSTMModel(LSTMConfig(vocab_size=VOCAB, hidden=24, num_layers=2,
                                 embed_dim=12))
    return LSTMDraftAdapter(model=model), model.init(jax.random.key(1))


def keys_for(n, seed=5):
    return jax.random.split(jax.random.key(seed), n)


# ---------------------------------------------------------------------------
# engine == oracle (the acceptance bit-exactness criterion)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("batch", [1, 3])
def test_transformer_engine_matches_oracle(tfm, batch):
    adapter, params = tfm
    eng = ARDraftEngine(adapter, params, max_len=24, temperature=0.9)
    keys = keys_for(batch)
    out = eng.generate_rows(keys, 8)
    ref = oracle_generate_rows(adapter, params, keys, 8, temperature=0.9,
                               max_len=24)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("prefix_len", [1, 3, 6])
@pytest.mark.slow
def test_engine_matches_oracle_across_prefill_lengths(tfm, prefix_len):
    adapter, params = tfm
    eng = ARDraftEngine(adapter, params, max_len=24)
    keys = keys_for(2)
    prompt = jax.random.randint(jax.random.key(9), (2, prefix_len), 0, VOCAB,
                                dtype=jnp.int32)
    out = eng.generate_rows(keys, 6, prompt=prompt)
    ref = oracle_generate_rows(adapter, params, keys, 6, prompt=prompt,
                               max_len=24)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.slow
def test_lstm_engine_matches_oracle(lstm):
    adapter, params = lstm
    eng = ARDraftEngine(adapter, params, max_len=32)
    keys = keys_for(3)
    out = eng.generate_rows(keys, 10)
    ref = oracle_generate_rows(adapter, params, keys, 10, max_len=32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # partial cache reuse: second call skips prefill, stays bit-exact
    out2 = eng.generate_rows(keys, 10)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
    assert eng.stats.prefill_computes == 1
    assert eng.stats.prefill_reuses == 1


@pytest.mark.parametrize("adapter_fixture", ["lstm", "tfm"])
def test_engine_with_donated_cache_matches_no_donation(
        request, monkeypatch, adapter_fixture):
    """Off the CPU the engine donates its cache buffers; every leaf must
    be its own buffer, and the drafts must not change."""
    adapter, params = request.getfixturevalue(adapter_fixture)
    keys = keys_for(3)
    ref = ARDraftEngine(adapter, params, max_len=16).generate_rows(keys, 6)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    eng = ARDraftEngine(adapter, params, max_len=16)
    for _ in range(2):                   # prefill, then the pooled prefix
        np.testing.assert_array_equal(
            np.asarray(eng.generate_rows(keys, 6)), np.asarray(ref))


@pytest.mark.slow
def test_partial_cache_reuse_is_bit_exact(tfm):
    """Prefix KV survives across calls (and across bucket switches); the
    reused-cache path must stay bit-identical to the oracle."""
    adapter, params = tfm
    eng = ARDraftEngine(adapter, params, max_len=24)
    keys = keys_for(2)
    prompt = jax.random.randint(jax.random.key(3), (2, 4), 0, VOCAB,
                                dtype=jnp.int32)
    ref8 = oracle_generate_rows(adapter, params, keys, 8, prompt=prompt,
                                max_len=24)
    out1 = eng.generate_rows(keys, 8, prompt=prompt)     # prefill compute
    out2 = eng.generate_rows(keys, 8, prompt=prompt)     # reuse (rewind)
    out3 = eng.generate_rows(keys, 5, prompt=prompt)     # reuse, new bucket
    out4 = eng.generate_rows(keys, 8, prompt=prompt)     # reuse again
    for out in (out1, out2, out4):
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref8))
    # drafts are prefix-stable: shorter bucket = prefix of the longer one
    np.testing.assert_array_equal(np.asarray(out3), np.asarray(ref8)[:, :5])
    assert eng.stats.prefill_computes == 1
    assert eng.stats.prefill_reuses == 3
    # a different prompt invalidates the pooled prefix
    other = jnp.zeros((2, 4), jnp.int32)
    eng.generate_rows(keys, 8, prompt=other)
    assert eng.stats.prefill_computes == 2


def test_generate_rows_is_pack_invariant(tfm):
    """Row b depends only on keys[b]: a subset of rows served in a
    smaller batch reproduces the same tokens bit-exactly."""
    adapter, params = tfm
    keys = keys_for(5)
    eng = ARDraftEngine(adapter, params, max_len=16)
    full = np.asarray(eng.generate_rows(keys, 6))
    sub = np.asarray(eng.generate_rows(keys[1:4], 6))
    np.testing.assert_array_equal(full[1:4], sub)


def test_batched_prefill_is_bit_identical_to_scan(tfm):
    """With the fixed-reduction-order decode kernel, the single
    multi-token batched prefill is BIT-identical to the one-token-at-a-
    time scan prefill — and it is the engine default for exact adapters."""
    adapter, params = tfm
    assert adapter.exact_batched_prefill
    keys = keys_for(2)
    prompt = jax.random.randint(jax.random.key(11), (2, 5), 0, VOCAB,
                                dtype=jnp.int32)
    a = ARDraftEngine(adapter, params, max_len=24,
                      prefill_mode="scan").generate_rows(
        keys, 6, prompt=prompt)
    b = ARDraftEngine(adapter, params, max_len=24,
                      prefill_mode="batched").generate_rows(
        keys, 6, prompt=prompt)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # default mode auto-picks batched for this adapter, same tokens
    c = ARDraftEngine(adapter, params, max_len=24).generate_rows(
        keys, 6, prompt=prompt)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_legacy_xla_decode_path_keeps_scan_default(tfm):
    """decode_impl='xla' opts out of the kernel path: batched prefill is
    only float-close there, so the engine default must fall back to
    scan prefill."""
    adapter, params = tfm
    xla_adapter = TransformerDraftAdapter(model=adapter.model,
                                          decode_impl="xla")
    assert not xla_adapter.exact_batched_prefill
    keys = keys_for(2)
    prompt = jax.random.randint(jax.random.key(11), (2, 5), 0, VOCAB,
                                dtype=jnp.int32)
    out = ARDraftEngine(xla_adapter, params, max_len=24).generate_rows(
        keys, 6, prompt=prompt)
    ref = ARDraftEngine(adapter, params, max_len=24,
                        prefill_mode="scan").generate_rows(
        keys, 6, prompt=prompt)
    assert np.asarray(out).shape == np.asarray(ref).shape == (2, 6)


def test_engine_validates_capacity_and_shapes(tfm):
    adapter, params = tfm
    eng = ARDraftEngine(adapter, params, max_len=8)
    with pytest.raises(ValueError, match="cache capacity"):
        eng.generate_rows(keys_for(2), 9)
    with pytest.raises(ValueError, match="prompt rows"):
        eng.generate_rows(keys_for(2), 4, prompt=jnp.zeros((3, 1), jnp.int32))
    with pytest.raises(ValueError, match="seq_len"):
        eng.generate_rows(keys_for(2), 0)
    with pytest.raises(ValueError, match="prefill_mode"):
        ARDraftEngine(adapter, params, max_len=8, prefill_mode="nope")


# ---------------------------------------------------------------------------
# quality scoring + calibration
# ---------------------------------------------------------------------------

def peaked_apply(params, tokens, t):
    """Toy backbone: p1 peaked on token 2 everywhere."""
    return jnp.zeros(tokens.shape + (VOCAB,)).at[..., 2].set(8.0)


def test_quality_scorer_orders_draft_tiers():
    scorer = make_quality_scorer(peaked_apply, None)
    good = jnp.full((4, 10), 2, jnp.int32)                 # on-mode drafts
    bad = jnp.full((4, 10), 7, jnp.int32)                  # off-mode drafts
    s_good, s_bad = np.asarray(scorer(good)), np.asarray(scorer(bad))
    assert (s_good > s_bad).all()


def test_fit_t0_calibration_monotone_and_clipped():
    data = np.full((64, 10), 2, np.int64)                  # "clean" corpus
    scorer = make_quality_scorer(peaked_apply, None)
    calib = fit_t0_calibration(scorer, data, VOCAB, num_per_tier=16)
    # anchors ascend in score, t0 non-decreasing
    assert list(calib.scores) == sorted(calib.scores)
    assert list(calib.t0s) == sorted(calib.t0s)
    # cleaner drafts get deeper t0
    assert calib.t0_for_score(calib.scores[-1] + 1.0) == calib.t0_ceil
    assert calib.t0_for_score(calib.scores[0] - 1.0) == calib.t0_floor
    lo, hi = calib.t0_for_scores([calib.scores[0], calib.scores[-1]])
    assert lo <= hi


def test_calibration_validation():
    with pytest.raises(ValueError, match="anchors"):
        T0Calibration(scores=(0.0,), t0s=(0.5,))
    with pytest.raises(ValueError, match="ascend"):
        T0Calibration(scores=(1.0, 0.0), t0s=(0.5, 0.9))
    with pytest.raises(ValueError, match="t0_floor"):
        T0Calibration(scores=(0.0, 1.0), t0s=(0.5, 0.9), t0_floor=0.9,
                      t0_ceil=0.5)


# ---------------------------------------------------------------------------
# measured cost ratio -> speedup accounting
# ---------------------------------------------------------------------------

def test_measure_cost_ratio_fields():
    x = jnp.zeros((4, 8), jnp.float32)
    rep = measure_cost_ratio(lambda: x + 1, lambda: x * 2, batch=4,
                             seq_len=8, iters=2, warmup=1)
    assert rep.draft_time_s > 0 and rep.nfe_time_s > 0
    assert rep.cost_ratio == pytest.approx(
        rep.draft_time_s / rep.nfe_time_s, rel=1e-6)
    assert rep.as_dict()["batch"] == 4


def test_ardraft_cost_ratio_measured_not_assumed():
    """Satellite: ARDraft.cost_ratio starts as a static estimate and is
    replaced by the measured draft-vs-NFE ratio, which then flows into
    speedup_report's effective_speedup."""
    draft = ARDraft(
        decode_fn=lambda params, rng, num, L: jnp.zeros((num, L), jnp.int32),
        params=None, seq_len=8)
    assert draft.cost_ratio == 0.02                       # estimate
    rep = draft.calibrate_cost_ratio(
        lambda: jnp.ones((4, 8)) * 3, rng=jax.random.key(0), num=4,
        seq_len=8, iters=2)
    assert draft.cost_ratio == rep.cost_ratio             # measured now
    sr = speedup_report(20, 0.8, draft_cost_ratio=draft.cost_ratio)
    assert sr.effective_speedup == pytest.approx(
        20 / (4 + draft.cost_ratio))
    assert sr.effective_speedup <= sr.nfe_speedup


def test_corruption_draft_keeps_zero_estimate_until_measured():
    data = np.zeros((8, 6), np.int64)
    d = CorruptionDraft(data=data, vocab_size=VOCAB)
    assert d.cost_ratio == 0.0
    d.calibrate_cost_ratio(lambda: jnp.zeros((2, 6)), rng=jax.random.key(0),
                           num=2, seq_len=6, iters=1)
    assert d.cost_ratio > 0.0
