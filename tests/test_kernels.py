"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU).

ws_step: the streamed vocab-tiled kernel is checked against BOTH oracles
with bit-identical in-kernel threefry noise reproduced host-side
(``threefry_gumbel``): the decomposed-score oracle
(``ws_step_ref_streamed``) and the probability-space oracle
(``ws_step_ref``) — across odd / non-128-multiple vocab sizes,
row_block padding remainders, multi-tile vocab walks, temperature != 1,
the final partial step, and a 262k vocab. flash_attn sweeps (seq, heads,
head_dim, GQA ratio, causal/bidir, window).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# optional dev dep (pip install -e .[dev]) — collection must never hard-error
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ModuleNotFoundError:
    HAS_HYPOTHESIS = False

from repro.core.paths import WarmStartPath
from repro.kernels.flash_attn import flash_attention, flash_attention_ref
from repro.kernels.ws_step import (
    make_ws_step_fn, pick_tiles, seed_from_key, threefry_gumbel, ws_step,
    ws_step_pallas, ws_step_ref, ws_step_ref_streamed,
    ws_step_streamed_pallas,
)


# ---------------------------------------------------------------------------
# ws_step — streamed vocab-tiled kernel
# ---------------------------------------------------------------------------

def run_streamed(seed, logits, x, a, *, row_block, vocab_tile,
                 temperature=1.0):
    """Pad + launch the streamed kernel in interpret mode, slice back."""
    r, v = logits.shape
    vp = -(-v // 128) * 128
    vp = -(-vp // vocab_tile) * vocab_tile
    lg = jnp.pad(logits.astype(jnp.float32), ((0, 0), (0, vp - v)))
    rp = -(-r // row_block) * row_block
    lg = jnp.pad(lg, ((0, rp - r), (0, 0)))
    xp = jnp.pad(x, (0, rp - r))
    ap = jnp.pad(a, (0, rp - r))
    out = ws_step_streamed_pallas(
        lg, xp[:, None].astype(jnp.int32), ap[:, None], seed,
        valid_v=v, row_block=row_block, vocab_tile=vocab_tile,
        temperature=temperature, interpret=True)
    return out[:r, 0]


@pytest.mark.parametrize("r,v", [(8, 128), (16, 300), (8, 27), (32, 1024),
                                 (3, 517), (5, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_streamed_kernel_matches_both_oracles(r, v, dtype):
    """Multi-tile walk (vocab_tile=128) vs the decomposed-score oracle
    (exact) and the probability-space oracle, with the kernel's own
    threefry noise reproduced host-side."""
    logits = (jax.random.normal(jax.random.key(r * v), (r, v)) * 3).astype(dtype)
    x = jax.random.randint(jax.random.key(1), (r,), 0, v)
    a = jax.random.uniform(jax.random.key(2), (r,))
    seed = jnp.array([1234, 567], jnp.int32)
    g = threefry_gumbel(seed, r, v)
    lf = logits.astype(jnp.float32)
    ref_s = ws_step_ref_streamed(lf, x, a, g)
    ref_p = ws_step_ref(lf, x, a, g)
    out = run_streamed(seed, lf, x, a, row_block=8, vocab_tile=128)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_s))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_p))


@pytest.mark.parametrize("temperature", [0.7, 2.3])
def test_streamed_kernel_temperature(temperature):
    r, v = 16, 517
    logits = jax.random.normal(jax.random.key(0), (r, v)) * 3
    x = jax.random.randint(jax.random.key(1), (r,), 0, v)
    a = jax.random.uniform(jax.random.key(2), (r,))
    seed = jnp.array([7, 8], jnp.int32)
    g = threefry_gumbel(seed, r, v)
    ref = ws_step_ref(logits, x, a, g, temperature=temperature)
    out = run_streamed(seed, logits, x, a, row_block=8, vocab_tile=128,
                       temperature=temperature)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_streamed_kernel_tiling_invariance():
    """Noise is keyed by absolute (row, col), so any (row_block,
    vocab_tile) must give the SAME samples — incl. row padding remainders."""
    r, v = 13, 1000   # 13 rows: remainders against every row_block below
    logits = jax.random.normal(jax.random.key(5), (r, v)) * 2
    x = jax.random.randint(jax.random.key(6), (r,), 0, v)
    a = jax.random.uniform(jax.random.key(7), (r,))
    seed = jnp.array([99, -3], jnp.int32)
    outs = [np.asarray(run_streamed(seed, logits, x, a, row_block=rb,
                                    vocab_tile=bv))
            for (rb, bv) in [(8, 128), (16, 128), (4, 256), (2, 512),
                             (16, 1024)]]
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def test_streamed_kernel_prng_reproducible():
    """Fixed seed -> identical draws; different seed -> different draws."""
    path = WarmStartPath(t0=0.5)
    b, n, v = 4, 8, 300
    logits = jax.random.normal(jax.random.key(0), (b, n, v))
    x = jax.random.randint(jax.random.key(1), (b, n), 0, v)
    t = jnp.full((b,), 0.7)
    h = jnp.asarray(0.1)
    o1 = ws_step(jax.random.key(2), logits, x, t, h, path)
    o2 = ws_step(jax.random.key(2), logits, x, t, h, path)
    o3 = ws_step(jax.random.key(3), logits, x, t, h, path)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert not bool((o1 == o3).all())


def test_streamed_kernel_262k_vocab_large_row_block():
    """The streamed kernel must take V = 262144 with row_block >= 8 (the
    seed kernel fell back to row_block=1 there)."""
    rb, bv = pick_tiles(64, 262144)
    assert rb >= 8 and 262144 % bv == 0
    path = WarmStartPath(t0=0.8)
    r, v = 8, 262144
    logits = jax.random.normal(jax.random.key(0), (1, r, v))
    x = jax.random.randint(jax.random.key(1), (1, r), 0, v)
    t = jnp.full((1,), 0.9)
    h = jnp.asarray(1.0 / 64)
    rng = jax.random.key(2)
    # hw_prng=False: host-noise parity must hold on TPU backends too
    out = ws_step(rng, logits, x, t, h, path, hw_prng=False)
    # parity vs the probability-space oracle on the same in-kernel noise
    tt = jnp.broadcast_to(t.reshape(-1, 1), (1, r)).reshape(r)
    a = jnp.clip(h * path.velocity_scale(tt), 0.0, 1.0)
    g = threefry_gumbel(seed_from_key(rng), r, v)
    ref = ws_step_ref(logits.reshape(r, v), x.reshape(r), a, g)
    np.testing.assert_array_equal(np.asarray(out.reshape(r)), np.asarray(ref))


def test_streamed_kernel_final_partial_step():
    """t + h > 1: the dispatcher clips a = h * scale(t) to 1 -> the step
    samples pure p1; must agree with the oracle at a = 1."""
    path = WarmStartPath(t0=0.0)
    r, v = 16, 300
    logits = jax.random.normal(jax.random.key(0), (r, v)) * 2
    x = jax.random.randint(jax.random.key(1), (r,), 0, v)
    t = jnp.full((r,), 0.98)
    h = jnp.asarray(0.05)           # t + h = 1.03 > 1
    rng = jax.random.key(4)
    out = ws_step(rng, logits, x, t, h, path, hw_prng=False)
    a = jnp.clip(h * path.velocity_scale(t), 0.0, 1.0)
    assert float(a.min()) == 1.0    # clipped: pure p1 draw
    g = threefry_gumbel(seed_from_key(rng), r, v)
    ref = ws_step_ref(logits, x, a, g)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_pick_tiles_vmem_budget():
    from repro.kernels.ws_step.ops import MAX_VOCAB_TILE, VMEM_BUDGET_BYTES
    for r, vp in [(8, 128), (64, 262144), (4096, 1024), (16, 33024)]:
        rb, bv = pick_tiles(r, vp)
        assert vp % bv == 0 and bv % 128 == 0 and bv <= MAX_VOCAB_TILE
        assert 16 * rb * bv <= VMEM_BUDGET_BYTES or rb == 1
    assert pick_tiles(64, 262144)[0] >= 8


def test_ws_step_wrapper_3d_and_guarantee_semantics():
    path = WarmStartPath(t0=0.5)
    b, n, v = 4, 6, 50
    logits = jax.random.normal(jax.random.key(0), (b, n, v)) * 2
    x = jax.random.randint(jax.random.key(1), (b, n), 0, v)
    out = ws_step(jax.random.key(2), logits, x, jnp.full((b,), 0.7),
                  jnp.asarray(0.05), path)
    assert out.shape == (b, n)
    assert int(out.min()) >= 0 and int(out.max()) < v


def test_ws_step_near_t1_moves_to_argmax():
    """At t -> 1, a -> 1 and the step samples ~p1; with peaked logits it
    must hit the mode."""
    path = WarmStartPath(t0=0.0)
    v = 33
    logits = jnp.zeros((8, 4, v)).at[..., 13].set(40.0)
    x = jnp.zeros((8, 4), jnp.int32)
    out = ws_step(jax.random.key(0), logits, x, jnp.full((8,), 0.999),
                  jnp.asarray(0.05), path)
    assert bool((out == 13).all())


def test_ws_step_a_zero_keeps_tokens():
    path = WarmStartPath(t0=0.0)
    logits = jax.random.normal(jax.random.key(0), (4, 5, 17))
    x = jax.random.randint(jax.random.key(1), (4, 5), 0, 17)
    out = ws_step(jax.random.key(2), logits, x, jnp.zeros((4,)),
                  jnp.asarray(0.0), path)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_ws_step_reference_impl_dispatch():
    path = WarmStartPath(t0=0.0)
    b, n, v = 2, 4, 40
    logits = jnp.zeros((b, n, v)).at[..., 9].set(30.0)
    x = jnp.zeros((b, n), jnp.int32)
    out = ws_step(jax.random.key(0), logits, x, jnp.full((b,), 0.99),
                  jnp.asarray(0.05), path, impl="reference")
    assert bool((out == 9).all())
    with pytest.raises(ValueError):
        ws_step(jax.random.key(0), logits, x, jnp.full((b,), 0.99),
                jnp.asarray(0.05), path, impl="nope")


def test_ws_step_fn_plugs_into_sampler():
    from repro.core.sampler import EulerSampler
    path = WarmStartPath(t0=0.8)
    step_fn = make_ws_step_fn(path)
    smp = EulerSampler(path=path, num_steps=20, step_fn=step_fn)
    target = 3

    def model_fn(xx, t):
        return jnp.zeros(xx.shape + (9,)).at[..., target].set(25.0)

    x0 = jax.random.randint(jax.random.key(0), (16, 4), 0, 9)
    x, stats = smp.sample(jax.random.key(1), model_fn, x0)
    assert int(stats.nfe) == 4
    assert float(jnp.mean((x == target).astype(jnp.float32))) > 0.9


# ---------------------------------------------------------------------------
# ws_step — legacy single-axis kernel (benchmark baseline)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,v", [(8, 128), (16, 300), (8, 27), (3, 517)])
def test_legacy_ws_step_kernel_matches_ref(r, v):
    logits = jax.random.normal(jax.random.key(0), (r, v)) * 3
    x = jax.random.randint(jax.random.key(1), (r,), 0, v)
    a = jax.random.uniform(jax.random.key(2), (r,))
    vp = -(-v // 128) * 128
    gumbel = jax.random.gumbel(jax.random.key(3), (r, vp), dtype=jnp.float32)
    rp = -(-r // 8) * 8
    lg = jnp.pad(logits.astype(jnp.float32), ((0, rp - r), (0, vp - v)))
    xp = jnp.pad(x, (0, rp - r))
    ap = jnp.pad(a, (0, rp - r))
    gp = jnp.pad(gumbel, ((0, rp - r), (0, 0)))
    out = ws_step_pallas(lg, xp[:, None].astype(jnp.int32), ap[:, None], gp,
                         valid_v=v, row_block=8, interpret=True)[:r, 0]
    ref = ws_step_ref(logits.astype(jnp.float32), x, a, gumbel[:r, :v])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,h,kh,d", [(128, 4, 4, 64), (200, 4, 2, 64),
                                      (96, 2, 2, 32), (256, 8, 1, 128)])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 64),
                                           (False, None), (False, 48)])
def test_flash_attention_sweep(s, h, kh, d, causal, window):
    b = 2
    q = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, kh, d))
    v = jax.random.normal(jax.random.key(2), (b, s, kh, d))
    out = flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    kk = jnp.repeat(k, h // kh, 2)
    vv = jnp.repeat(v, h // kh, 2)
    ref = flash_attention_ref(q, kk, vv, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    b, s, h, d = 1, 128, 2, 64
    q = jax.random.normal(jax.random.key(0), (b, s, h, d)).astype(dtype)
    k = jax.random.normal(jax.random.key(1), (b, s, h, d)).astype(dtype)
    v = jax.random.normal(jax.random.key(2), (b, s, h, d)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


def test_flash_attention_matches_model_attention_path():
    """Cross-check against models/attention.py XLA semantics."""
    from repro.models.attention import attn_mask, NEG_INF
    b, s, h, d = 1, 64, 2, 32
    q = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, h, d))
    v = jax.random.normal(jax.random.key(2), (b, s, h, d))
    out = flash_attention(q, k, v, causal=True, window=16, interpret=True)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    mask = attn_mask(pos, pos, mode="causal", window=16)
    sc = jnp.einsum("bshd,bthd->bhst", q, k) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    sc = jnp.where(mask[:, None], sc, NEG_INF)
    ref = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(sc, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------------------------------------------------------------------
# the fused bidirectional path of the refine (models/attention.py)
# ---------------------------------------------------------------------------

# query heads, KV heads, head width
HEAD_SHAPES = {"dfm-dit": (12, 12, 64), "starcoder2-3b": (24, 2, 128)}
ATOL = {jnp.float32: 3e-5, jnp.bfloat16: 2e-2}


def _qkv(b, s, h, kh, d, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kh, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kh, d)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("grid", ["one-key-block", "multi-key-block"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("s", [128, 512])
@pytest.mark.parametrize("heads", sorted(HEAD_SHAPES))
def test_fused_bidirectional_matches_sdpa(heads, s, dtype, grid):
    """The fused path against ``_sdpa`` at the cells' head shapes: the
    served grid (the whole key range in one block) and the online-softmax
    grid over several key blocks."""
    from repro.kernels.flash_attn.kernel import heads_per_block, pick_blocks
    from repro.models.attention import _sdpa_bidir, fused_attention
    h, kh, d = HEAD_SHAPES[heads]
    q, k, v = _qkv(1, s, h, kh, d, dtype)
    scale = d ** -0.5
    if grid == "one-key-block":
        hb = heads_per_block(h, d)
        assert pick_blocks(s, s, hb * d, jnp.dtype(dtype).itemsize,
                           hb) == (s, s)
        out = fused_attention(q, k, v, scale)
    else:
        out = flash_attention(q, k, v, causal=False, scale=scale,
                              block_q=s // 2, block_k=s // 4, interpret=True)
    ref = _sdpa_bidir(q, k, v, scale)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=ATOL[dtype])


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("blocks", [(None, None), (64, 32)],
                         ids=["one-key-block", "multi-key-block"])
def test_flash_attention_gqa_index_map_equals_repeat(blocks, causal):
    """KV heads read through the index map (heads of 128 lanes, as
    StarCoder2-3B's) give the bits that K and V repeated to every query
    head give."""
    q, k, v = _qkv(2, 128, 8, 2, 128, jnp.float32)
    bq, bk = blocks
    kw = dict(causal=causal, block_q=bq, block_k=bk, interpret=True)
    out = flash_attention(q, k, v, **kw)
    rep = flash_attention(q, jnp.repeat(k, 4, 2), jnp.repeat(v, 4, 2), **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(rep))


def test_fused_attention_mxu_bf16_is_einsum_default_on_tpu():
    """Float32 inputs with bf16 matmul operands (the TPU's default-precision
    einsum): ``_sdpa``'s arithmetic with q, k, the normalised
    probabilities and v each rounded to bf16 and float32 accumulation.
    Sums in another order move a probability by a float32 ulp, which can
    carry one lying on a bf16 rounding boundary to its neighbour: at most
    one bf16 ulp (2**-7 relative) of the largest probability times the
    largest |v|."""
    q, k, v = _qkv(2, 256, 4, 2, 64, jnp.float32)
    bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    out = flash_attention(q, k, v, causal=False, mxu_dtype=jnp.bfloat16,
                          interpret=True)
    hi = jax.lax.Precision.HIGHEST
    kk, vv = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    sc = jnp.einsum("bshd,bthd->bhst", bf(q), bf(kk), precision=hi) * 64 ** -0.5
    p = jax.nn.softmax(sc, axis=-1)
    ref = jnp.einsum("bhst,bthd->bshd", bf(p), bf(vv), precision=hi)
    flip = 2.0 ** -7 * float(p.max()) * float(jnp.abs(v).max())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=max(3e-5, flip))


def test_fused_attention_grad_matches_sdpa():
    """``jax.grad`` through the custom VJP is ``_sdpa``'s gradient."""
    from repro.models.attention import _sdpa_bidir, fused_attention
    q, k, v = _qkv(2, 128, 4, 2, 32, jnp.float32)
    w = jax.random.normal(jax.random.key(9), q.shape)
    scale = 32 ** -0.5

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, scale) * w)

    val, got = jax.value_and_grad(loss(fused_attention), (0, 1, 2))(q, k, v)
    want_val, want = jax.value_and_grad(loss(_sdpa_bidir), (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(val), float(want_val), rtol=1e-5)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-6)


AUTO_RULE = [
    ({}, "fused"),
    (dict(seq=128), "fused"),
    (dict(seq=64), "xla"),
    (dict(seq=32), "xla"),
    (dict(seq=192), "xla"),
    (dict(backend="cpu"), "xla"),
    (dict(backend="gpu"), "xla"),
    (dict(devices=4), "xla"),
    (dict(mode="causal"), "xla"),
    (dict(cached=True), "xla"),
    (dict(window=512), "xla"),
    (dict(window=1024), "fused"),
    (dict(window=4096), "fused"),
    (dict(softcap=30.0), "xla"),
    (dict(impl="xla"), "xla"),
    (dict(impl="chunked", seq=2048), "chunked"),
    (dict(impl="chunked"), "xla"),
]


@pytest.mark.parametrize("change,want", AUTO_RULE,
                         ids=[",".join(f"{k}={v}" for k, v in c.items())
                              or "served" for c, _ in AUTO_RULE])
def test_attention_impl_auto_rule(change, want):
    """``"auto"`` fuses only a bidirectional, uncached, unsoftcapped call
    of a multiple of 128 queries on one TPU, with no narrower window."""
    from repro.configs.dfm_dit import CONFIG
    from repro.models.attention import attention_impl
    args = dict(impl="auto", softcap=0.0, backend="tpu", devices=1,
                mode="bidir", cached=False, window=None, seq=1024)
    args.update(change)
    cfg = CONFIG.replace(attn_impl=args.pop("impl"), attn_chunk=1024,
                         attn_logit_softcap=args.pop("softcap"))
    assert attention_impl(cfg, **args) == want


@pytest.mark.parametrize("mesh_size,want", [(None, "fused"), (1, "fused"),
                                            (4, "xla")])
def test_attention_impl_reads_the_mesh_in_scope(mesh_size, want):
    """Without ``devices``, the rule counts the devices of the mesh that
    ``axis_rules`` put in scope: a batch-sharded mesh keeps ``_sdpa``."""
    from types import SimpleNamespace
    from repro.configs.dfm_dit import CONFIG
    from repro.distributed.sharding import SERVE_RULES, axis_rules
    from repro.models.attention import attention_impl
    mesh = None if mesh_size is None else SimpleNamespace(size=mesh_size)
    with axis_rules(SERVE_RULES, mesh):
        got = attention_impl(CONFIG, mode="bidir", cached=False, window=None,
                             seq=256, backend="tpu")
    assert got == want
    assert CONFIG.attn_impl == "auto"


def test_attention_rule_follows_the_default_device(monkeypatch):
    """On a TPU machine, a forward traced under ``jax.default_device(cpu)``
    (a host-side reference) runs on the CPU: the rule keeps ``_sdpa`` and
    kernels resolve to interpret mode there."""
    from repro.configs.dfm_dit import CONFIG
    from repro.kernels import default_platform, resolve_interpret
    from repro.models.attention import attention_impl
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rule = functools.partial(attention_impl, CONFIG, mode="bidir",
                             cached=False, window=None, seq=1024)
    assert default_platform() == "tpu" and rule() == "fused"
    assert resolve_interpret(None) is False
    with jax.default_device(jax.devices("cpu")[0]):
        assert default_platform() == "cpu" and rule() == "xla"
        assert resolve_interpret(None) is True


def test_model_forward_fused_matches_xla(monkeypatch):
    """``dfm_apply`` with the rule answering as on a TPU (the kernel in
    interpret mode) gives ``attn_impl="xla"``'s logits, and records the
    attention each GQA call took."""
    from repro.configs.dfm_dit import tiny_config
    from repro.models import attention, build_model
    cfg = tiny_config(vocab_size=27, seq_len=128).replace(
        num_layers=2, d_model=128, num_heads=2, num_kv_heads=2, d_ff=256)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (2, 128), 0, 27)
    t = jnp.full((2,), 0.8)
    want = build_model(cfg.replace(attn_impl="xla")).dfm_apply(params, x, t)
    monkeypatch.setattr(attention, "attention_impl", functools.partial(
        attention.attention_impl, backend="tpu"))
    with attention.record_attention() as taken:
        got = jax.jit(model.dfm_apply)(params, x, t)
    assert taken and set(taken) == {"fused"}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=1e-4)


if HAS_HYPOTHESIS:

    @given(st.integers(16, 160), st.integers(0, 1))
    @settings(max_examples=8, deadline=None)
    def test_flash_attention_property_random_seq(s, causal_flag):
        q = jax.random.normal(jax.random.key(s), (1, s, 2, 32))
        k = jax.random.normal(jax.random.key(s + 1), (1, s, 2, 32))
        v = jax.random.normal(jax.random.key(s + 2), (1, s, 2, 32))
        out = flash_attention(q, k, v, causal=bool(causal_flag), interpret=True)
        ref = flash_attention_ref(q, k, v, causal=bool(causal_flag))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)
