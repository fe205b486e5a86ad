"""Euler CTMC sampler tests (core/sampler.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.guarantees import warm_nfe
from repro.core.paths import WarmStartPath
from repro.core.sampler import (
    EulerSampler, categorical_from_probs, euler_step_probs, refine_schedule,
)


def test_step_probs_are_distribution():
    path = WarmStartPath(t0=0.5)
    logits = jax.random.normal(jax.random.key(0), (4, 3, 11))
    x = jax.random.randint(jax.random.key(1), (4, 3), 0, 11)
    for t in (0.5, 0.9, 0.999):
        p = euler_step_probs(logits, x, jnp.full((4,), t), jnp.asarray(0.05), path)
        assert float(jnp.abs(p.sum(-1) - 1.0).max()) < 1e-5
        assert float(p.min()) >= 0.0


def test_step_prob_limits():
    """a -> 0 keeps the current token; a -> 1 moves to p1."""
    path = WarmStartPath(t0=0.0)
    logits = jnp.zeros((1, 1, 5)).at[0, 0, 2].set(50.0)
    x = jnp.array([[4]], dtype=jnp.int32)
    p_stay = euler_step_probs(logits, x, jnp.array([0.0]), jnp.asarray(1e-9), path)
    assert float(p_stay[0, 0, 4]) == pytest.approx(1.0, abs=1e-5)
    # at t ~ 1 the clip makes a = 1 -> pure p1
    p_move = euler_step_probs(logits, x, jnp.array([0.999]), jnp.asarray(0.05), path)
    assert float(p_move[0, 0, 2]) == pytest.approx(1.0, abs=1e-3)


def test_categorical_from_probs_statistics():
    probs = jnp.broadcast_to(jnp.array([0.1, 0.2, 0.7]), (20000, 3))
    out = categorical_from_probs(jax.random.key(0), probs)
    freq = np.bincount(np.asarray(out), minlength=3) / 20000
    np.testing.assert_allclose(freq, [0.1, 0.2, 0.7], atol=0.02)


@pytest.mark.parametrize("t0,expected", [(0.0, 20), (0.5, 10), (0.8, 4), (0.9, 2)])
def test_sampler_nfe(t0, expected):
    smp = EulerSampler(path=WarmStartPath(t0=t0), num_steps=20)
    assert smp.nfe == expected
    calls = []

    def model_fn(x, t):
        calls.append(1)
        return jnp.zeros(x.shape + (7,))

    x0 = jnp.zeros((2, 3), jnp.int32)
    x, stats = smp.sample(jax.random.key(0), model_fn, x0)
    assert int(stats.nfe) == expected
    assert x.shape == x0.shape


def test_sampler_converges_to_model_distribution():
    """With a constant p1 concentrated on one token, the sampler must land
    every token there by t = 1 (the CTMC transports to p1)."""
    v = 9
    target = 5

    def model_fn(x, t):
        return jnp.zeros(x.shape + (v,)).at[..., target].set(25.0)

    smp = EulerSampler(path=WarmStartPath(t0=0.0), num_steps=24)
    x0 = jax.random.randint(jax.random.key(2), (64, 4), 0, v)
    x, _ = smp.sample(jax.random.key(3), model_fn, x0)
    assert float(jnp.mean((x == target).astype(jnp.float32))) > 0.97


def test_warm_start_equals_cold_given_good_draft():
    """Warm start from near-target drafts reaches the same terminal set."""
    v = 9
    target = 3

    def model_fn(x, t):
        return jnp.zeros(x.shape + (v,)).at[..., target].set(25.0)

    warm = EulerSampler(path=WarmStartPath(t0=0.8), num_steps=24)
    drafts = jax.random.randint(jax.random.key(4), (64, 4), 0, v)
    x, stats = warm.sample(jax.random.key(5), model_fn, drafts)
    assert int(stats.nfe) == 5  # ceil(24 * 0.2)
    assert float(jnp.mean((x == target).astype(jnp.float32))) > 0.95


def test_custom_step_fn_plugs_in():
    hits = []

    def step_fn(rng, logits, x_t, t, h):
        hits.append(1)
        return x_t

    smp = EulerSampler(path=WarmStartPath(t0=0.5), num_steps=4, step_fn=step_fn)
    x0 = jnp.zeros((2, 3), jnp.int32)
    smp.sample(jax.random.key(0), lambda x, t: jnp.zeros(x.shape + (5,)), x0)
    assert hits  # traced at least once


# ---------------------------------------------------------------------------
# refine_schedule edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t0", [0.95, 0.99, 0.999])
def test_refine_schedule_t0_near_one(t0):
    """Near t0 = 1 the warm start collapses to a single partial step that
    still lands exactly on t = 1."""
    cold_nfe = 20
    n = warm_nfe(cold_nfe, t0)
    assert n == 1
    ts, hs = refine_schedule(t0, 1.0 / cold_nfe, n)
    assert ts.shape == hs.shape == (1,)
    assert ts[0] == pytest.approx(t0)
    assert hs[0] > 0.0
    assert ts[0] + hs[0] == pytest.approx(1.0, abs=1e-6)


def test_refine_schedule_n_equals_one_full_interval():
    """cold_nfe = 1: one step covers the whole remaining interval."""
    ts, hs = refine_schedule(0.5, 1.0, warm_nfe(1, 0.5))
    assert ts.shape == (1,)
    assert ts[0] == pytest.approx(0.5)
    assert hs[0] == pytest.approx(0.5)     # min(h=1.0, 1 - 0.5)


@pytest.mark.parametrize("t0,cold_nfe", [(0.8, 7), (0.3, 9), (0.65, 11), (0.0, 5)])
def test_refine_schedule_partial_final_step_lands_on_one(t0, cold_nfe):
    h = 1.0 / cold_nfe
    n = warm_nfe(cold_nfe, t0)
    ts, hs = refine_schedule(t0, h, n)
    assert len(ts) == n
    # all steps positive, none larger than the cold step size
    assert np.all(hs > 0) and np.all(hs <= np.float32(h) + 1e-7)
    # full-size steps everywhere except the (possibly partial) last
    np.testing.assert_allclose(hs[:-1], h, rtol=1e-5)
    # the last step lands exactly on t = 1
    assert ts[-1] + hs[-1] == pytest.approx(1.0, abs=1e-6)
    # times are the uniform grid from t0
    np.testing.assert_allclose(ts, t0 + np.arange(n) * h, rtol=1e-5, atol=1e-7)


def test_named_scopes_leave_refine_tokens_bit_identical():
    """``scan_refine_loop_rows`` tags its body ``backbone`` and
    ``sample_step``; the tokens equal those of the same loop written
    without scopes, bit for bit, and the compiled program keeps both
    scopes in its op metadata."""
    from repro.configs.dfm_dit import tiny_config
    from repro.core.sampler import (
        make_euler_one_step_rows, refine_schedule_rows, scan_refine_loop_rows,
    )
    from repro.models import build_model

    cfg = tiny_config(vocab_size=27, seq_len=16).replace(
        num_layers=1, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    one_step = make_euler_one_step_rows(WarmStartPath(t0=0.0))
    ts, hs, active, key_idx, _ = refine_schedule_rows(
        np.array([0.5, 0.8, 0.8, 0.9]), 1 / 16, 16)
    keys = jax.random.split(jax.random.key(3), 4)
    x0 = jax.random.randint(jax.random.key(4), (4, 16), 0, 27)
    logits_fn = lambda x, t: model.dfm_apply(params, x, t)  # noqa: E731

    def plain(x, keys, ts, hs, active, key_idx):
        def body(x, inp):
            t, h, act, idx = inp
            k = jax.vmap(jax.random.fold_in)(keys, idx)
            x_next = one_step(k, logits_fn(x, t), x, t, h)
            return jnp.where(act[:, None], x_next, x), None
        return jax.lax.scan(body, x, (ts, hs, active, key_idx))[0]

    def scoped(x, keys, ts, hs, active, key_idx):
        return scan_refine_loop_rows(logits_fn, one_step, x, keys, ts, hs,
                                     active, key_idx)

    args = (x0, keys, jnp.asarray(ts), jnp.asarray(hs), jnp.asarray(active),
            jnp.asarray(key_idx))
    got = jax.jit(scoped)(*args)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jax.jit(plain)(*args)))
    text = jax.jit(scoped).lower(*args).compile().as_text()
    assert "/backbone/" in text and "/sample_step/" in text
