"""Pallas TPU kernels: fused warm-start Euler sampling step.

Two generations of the kernel live here:

``ws_step_pallas`` — the original single-axis kernel (grid over row blocks,
whole vocab resident in VMEM, Gumbel noise pre-drawn into an (R, V) HBM
tensor).  Kept as the baseline the benchmarks compare against and as a
secondary oracle for the streamed kernel.

``ws_step_streamed_pallas`` — the streamed, vocab-tiled rewrite.  A 2-D
grid over ``(row_block, vocab_tile)`` walks the vocabulary in VMEM-sized
tiles keeping flash-style online-softmax accumulators ``(m, s)`` and a
running Gumbel-argmax in VMEM scratch, so arbitrary vocab sizes (262k+)
run with large row blocks and the logits are the *only* (R, V) HBM read
per step.  The Gumbel noise is generated in-kernel — via the TPU hardware
PRNG (``pltpu.prng_seed`` / ``prng_random_bits``) on real TPUs, or via a
counter-based threefry2x32 implemented in jnp ops for interpret/CPU
parity — which removes the (R, V) HBM Gumbel tensor entirely and roughly
halves per-step HBM traffic.

Streaming decomposition.  The step samples

    x' = argmax_v log(max((1-a)*onehot(x)[v] + a*p1[v], eps)) + g[v]

with ``p1 = softmax(logits / T)``.  Split the argmax into ``v != x`` and
``v == x``.  For ``v != x`` the score is ``log a + (lg_v - m) - log s +
g_v`` whose argmax over v is the argmax of ``lg_v + g_v`` — a quantity
that needs *no* softmax normaliser, so it streams: each tile updates a
running ``best = max(lg + g)`` / ``best_idx`` (with column x masked out)
while ``(m, s)`` accumulate online.  The single ``v == x`` column is
captured into scratch when its tile passes by.  The final tile resolves

    score_other = log(max(a, eps)) + best - m - log s
    score_x     = log(max((1-a) + a * exp(lg_x - m)/s, eps)) + g_x
    x'          = x  if score_x >= score_other else best_idx.

See README.md in this directory for the tiling/VMEM budget math.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MIN_PROB = 1e-30
NEG = -1e30


# ---------------------------------------------------------------------------
# Counter-based PRNG (threefry2x32), shared by the kernel's interpret/CPU
# path and the host-side oracle so parity tests see bit-identical noise.
# ---------------------------------------------------------------------------

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << r) | (x >> (32 - r))


def _round4(x0, x1, rots):
    for r in rots:
        x0 = x0 + x1
        x1 = _rotl(x1, r)
        x1 = x0 ^ x1
    return x0, x1


def threefry2x32(k0, k1, c0, c1):
    """threefry-2x32 (20 rounds, JAX parameterisation) on uint32 arrays.

    ``(k0, k1)`` key words, ``(c0, c1)`` counter words; broadcasts like
    jnp arithmetic. Returns the two output words.
    """
    one = jnp.uint32(1)
    ks2 = k0 ^ k1 ^ jnp.uint32(0x1BD11BDA)
    x0 = c0 + k0
    x1 = c1 + k1
    x0, x1 = _round4(x0, x1, _ROTATIONS[0])
    x0 = x0 + k1
    x1 = x1 + ks2 + one
    x0, x1 = _round4(x0, x1, _ROTATIONS[1])
    x0 = x0 + ks2
    x1 = x1 + k0 + jnp.uint32(2)
    x0, x1 = _round4(x0, x1, _ROTATIONS[0])
    x0 = x0 + k0
    x1 = x1 + k1 + jnp.uint32(3)
    x0, x1 = _round4(x0, x1, _ROTATIONS[1])
    x0 = x0 + k1
    x1 = x1 + ks2 + jnp.uint32(4)
    x0, x1 = _round4(x0, x1, _ROTATIONS[0])
    x0 = x0 + ks2
    x1 = x1 + k0 + jnp.uint32(5)
    return x0, x1


def gumbel_from_bits(bits: jax.Array) -> jax.Array:
    """uint32 bits -> standard Gumbel(0, 1) float32, u strictly in (0, 1).

    The top 24 bits go through int32 (Mosaic has no uint32 -> float32
    cast); below 2**24 that is the same value.
    """
    u = ((bits >> 8).astype(jnp.int32).astype(jnp.float32) + 0.5) * (
        1.0 / (1 << 24))
    return -jnp.log(-jnp.log(u))


# 0x9E3779B9 as int32: odd, so ``n -> n * _GOLDEN`` is a bijection mod 2**32
_GOLDEN = -1640531527


def hw_prng_bits(seed0, seed1, block, tile, shape) -> jax.Array:
    """uint32 bits from the TPU hardware PRNG for one grid program.

    Mosaic takes at most two seed words, so the grid coordinates are
    folded into them: distinct ``(block, tile)`` pairs give distinct
    seeds under one key.
    """
    pltpu.prng_seed(seed0 ^ (block * _GOLDEN), seed1 ^ (tile * _GOLDEN))
    return pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)


def threefry_gumbel(seed: jax.Array, rows: int, cols: int) -> jax.Array:
    """Host-side replica of the streamed kernel's threefry noise path.

    ``seed`` is the (2,) int32/uint32 seed the dispatcher derives from the
    PRNG key. Noise is keyed by *absolute* (row, col) coordinates, so it
    is independent of the (row_block, vocab_tile) tiling — the parity and
    tiling-invariance tests rely on this.
    """
    seed = jnp.asarray(seed).astype(jnp.uint32)
    r0 = jnp.arange(rows, dtype=jnp.uint32)[:, None]
    c0 = jnp.arange(cols, dtype=jnp.uint32)[None, :]
    bits, _ = threefry2x32(seed[0], seed[1], r0, c0)
    return gumbel_from_bits(bits)


# ---------------------------------------------------------------------------
# Streamed vocab-tiled kernel
# ---------------------------------------------------------------------------


def _ws_step_streamed_kernel(
    seed_ref,          # SMEM (2,) int32
    logits_ref,        # VMEM (BR, BV)
    x_ref,             # VMEM (BR, 1) int32
    a_ref,             # VMEM (BR, 1) f32
    out_ref,           # VMEM (BR, 1) int32
    m_ref, s_ref, best_ref, bidx_ref, xlg_ref, xg_ref,   # VMEM scratch (BR, 1)
    *,
    temperature: float,
    valid_v: int,
    nj: int,
    use_hw_prng: bool,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    br, bv = logits_ref.shape

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        best_ref[...] = jnp.full_like(best_ref, NEG)
        bidx_ref[...] = jnp.zeros_like(bidx_ref)
        xlg_ref[...] = jnp.zeros_like(xlg_ref)
        xg_ref[...] = jnp.zeros_like(xg_ref)

    lg = logits_ref[...].astype(jnp.float32) / temperature
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    valid = col < valid_v
    lg = jnp.where(valid, lg, NEG)

    # -- in-kernel Gumbel noise: no (R, V) HBM tensor ----------------------
    if use_hw_prng:
        bits = hw_prng_bits(seed_ref[0], seed_ref[1], i, j, (br, bv))
    else:
        rows = i * br + jax.lax.broadcasted_iota(jnp.int32, (br, bv), 0)
        bits, _ = threefry2x32(
            seed_ref[0].astype(jnp.uint32), seed_ref[1].astype(jnp.uint32),
            rows.astype(jnp.uint32), col.astype(jnp.uint32),
        )
    g = gumbel_from_bits(bits)

    x = x_ref[...]                      # (BR, 1)
    isx = col == x                      # (BR, BV)

    # capture the v == x column when its tile passes (exactly one hit/row)
    xlg_ref[...] += jnp.sum(jnp.where(isx, lg, 0.0), axis=1, keepdims=True)
    xg_ref[...] += jnp.sum(jnp.where(isx, g, 0.0), axis=1, keepdims=True)

    # online softmax accumulators
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(lg, axis=1, keepdims=True))
    s_ref[...] = (
        s_ref[...] * jnp.exp(m_prev - m_new)
        + jnp.sum(jnp.exp(lg - m_new), axis=1, keepdims=True)
    )
    m_ref[...] = m_new

    # running Gumbel-argmax over v != x (normaliser-free: see module doc)
    cand = jnp.where(isx | jnp.logical_not(valid), NEG, lg + g)
    tile_best = jnp.max(cand, axis=1, keepdims=True)
    tile_arg = j * bv + jnp.argmax(cand, axis=1).astype(jnp.int32)[:, None]
    better = tile_best > best_ref[...]
    bidx_ref[...] = jnp.where(better, tile_arg, bidx_ref[...])
    best_ref[...] = jnp.maximum(best_ref[...], tile_best)

    @pl.when(j == nj - 1)
    def _finalize():
        a = a_ref[...]
        m = m_ref[...]
        s = s_ref[...]
        log_s = jnp.log(s)
        score_other = (
            jnp.log(jnp.maximum(a, MIN_PROB)) + best_ref[...] - m - log_s
        )
        p1x = jnp.exp(xlg_ref[...] - m) / s
        px = (1.0 - a) + a * p1x
        score_x = jnp.log(jnp.maximum(px, MIN_PROB)) + xg_ref[...]
        out_ref[...] = jnp.where(
            score_x >= score_other, x, bidx_ref[...]
        ).astype(jnp.int32)


def ws_step_streamed_pallas(
    logits: jax.Array,      # (R, Vp) — V padded to a multiple of vocab_tile
    x_t: jax.Array,         # (R, 1) int32
    a: jax.Array,           # (R, 1) float32
    seed: jax.Array,        # (2,) int32 PRNG seed words
    *,
    valid_v: int,
    row_block: int,
    vocab_tile: int,
    temperature: float = 1.0,
    use_hw_prng: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Streamed warm-start Euler step over a 2-D (rows, vocab) grid."""
    r, vp = logits.shape
    assert r % row_block == 0, (r, row_block)
    assert vp % vocab_tile == 0, (vp, vocab_tile)
    nj = vp // vocab_tile
    grid = (r // row_block, nj)
    kernel = functools.partial(
        _ws_step_streamed_kernel,
        temperature=temperature, valid_v=valid_v, nj=nj,
        use_hw_prng=use_hw_prng,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((row_block, vocab_tile), lambda i, j: (i, j)),
            pl.BlockSpec((row_block, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((row_block, 1), jnp.float32),   # m
            pltpu.VMEM((row_block, 1), jnp.float32),   # s
            pltpu.VMEM((row_block, 1), jnp.float32),   # best
            pltpu.VMEM((row_block, 1), jnp.int32),     # best idx
            pltpu.VMEM((row_block, 1), jnp.float32),   # lg at x
            pltpu.VMEM((row_block, 1), jnp.float32),   # gumbel at x
        ],
        interpret=interpret,
    )(jnp.asarray(seed, jnp.int32), logits, x_t, a)


# ---------------------------------------------------------------------------
# Legacy single-axis kernel (pre-drawn HBM Gumbel) — benchmark baseline
# ---------------------------------------------------------------------------


def _ws_step_kernel(logits_ref, x_ref, a_ref, gumbel_ref, out_ref, *,
                    temperature: float, valid_v: int):
    """One (BR, V) tile: next-token sampling with pre-drawn Gumbel noise."""
    lg = logits_ref[...].astype(jnp.float32) / temperature
    br, v = lg.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (br, v), 1)
    valid = col < valid_v
    lg = jnp.where(valid, lg, NEG)

    # softmax over the vocab tile (numerically stable)
    m = jnp.max(lg, axis=-1, keepdims=True)
    e = jnp.exp(lg - m)
    p1 = e / jnp.sum(e, axis=-1, keepdims=True)

    x = x_ref[...]                     # (BR, 1)
    a = a_ref[...].astype(jnp.float32)  # (BR, 1)
    onehot = (col == x).astype(jnp.float32)
    probs = (1.0 - a) * onehot + a * p1

    score = jnp.log(jnp.maximum(probs, MIN_PROB)) + gumbel_ref[...]
    score = jnp.where(valid, score, NEG)
    out_ref[...] = jnp.argmax(score, axis=-1).astype(jnp.int32)[:, None]


def ws_step_pallas(
    logits: jax.Array,      # (R, Vp) — V padded to 128 lanes
    x_t: jax.Array,         # (R, 1) int32
    a: jax.Array,           # (R, 1) float32
    gumbel: jax.Array,      # (R, Vp) float32
    *,
    valid_v: int,
    row_block: int = 8,
    temperature: float = 1.0,
    interpret: bool = False,
) -> jax.Array:
    r, vp = logits.shape
    assert r % row_block == 0, (r, row_block)
    grid = (r // row_block,)
    kernel = functools.partial(
        _ws_step_kernel, temperature=temperature, valid_v=valid_v
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_block, vp), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
            pl.BlockSpec((row_block, vp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        interpret=interpret,
    )(logits, x_t, a, gumbel)
