"""Pallas TPU megakernel: K fused warm-start Euler sampling steps.

One ``pallas_call`` executes K consecutive warm-start sampling steps
against a logits buffer that is written to HBM once per fused block
(one backbone evaluation), instead of K separate ``ws_step`` dispatches
each re-materialising per-step (R,) token buffers in HBM. The per-row
token state lives in VMEM scratch across steps; each step streams the
vocabulary in VMEM-sized tiles with exactly the discipline of
``ws_step/kernel.py`` — online-softmax accumulators ``(m, s)``, a
running normaliser-free Gumbel-argmax over ``v != x``, the ``v == x``
column captured in scratch, and in-kernel PRNG (hardware PRNG on real
TPUs, counter-based threefry2x32 for interpret/CPU parity).

Grid layout: ``(row_blocks, K, vocab_tiles)`` with the vocab axis
innermost, so for each row block the kernel walks all tiles of step 0,
finalises the step's token draw into the ``x`` scratch, then walks step
1's tiles against the updated state, and so on. The token buffer only
touches HBM twice per block: the initial read and the final write.
When the (padded) vocab fits a single tile the logits block index never
changes, so the logits are read from HBM once for ALL K steps.

Per-step inputs ``a`` (mixing weight) and the PRNG seed words are
carried as full K-slabs per row block — this is the K-dependent VMEM
term ``pick_tiles_fused`` budgets for. A step with ``a == 0`` provably
freezes its rows bit-exactly (``score_x = g_x >= ~-2.9`` vs
``score_other <= log(1e-30) + g_max - log s <= ~-52``), which is how
partial-K tail blocks and per-row heterogeneous-t0 entry masks are
expressed without any extra masking machinery.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ws_step.kernel import (
    MIN_PROB, NEG, gumbel_from_bits, hw_prng_bits, threefry2x32,
)


def _ws_fused_kernel(
    seed_ref,          # threefry: VMEM (K, BR, 2) int32; hw: SMEM (K, 2)
    logits_ref,        # VMEM (BR, BV)
    x_ref,             # VMEM (BR, 1) int32 — initial tokens
    a_ref,             # VMEM (K, BR, 1) f32 — per-step mixing weights
    ctr_ref,           # VMEM (BR, 1) int32 — per-row noise counter word
    out_ref,           # VMEM (BR, 1) int32 — final tokens
    xs_ref,            # VMEM scratch (BR, 1) int32 — carried token state
    m_ref, s_ref, best_ref, bidx_ref, xlg_ref, xg_ref,   # (BR, 1) scratch
    *,
    temperature: float,
    valid_v: int,
    num_steps: int,
    nvt: int,
    use_hw_prng: bool,
):
    i = pl.program_id(0)       # row block
    j = pl.program_id(1)       # fused step
    k = pl.program_id(2)       # vocab tile
    br, bv = logits_ref.shape

    @pl.when((j == 0) & (k == 0))
    def _load_tokens():
        xs_ref[...] = x_ref[...]

    @pl.when(k == 0)
    def _init_step():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        best_ref[...] = jnp.full_like(best_ref, NEG)
        bidx_ref[...] = jnp.zeros_like(bidx_ref)
        xlg_ref[...] = jnp.zeros_like(xlg_ref)
        xg_ref[...] = jnp.zeros_like(xg_ref)

    lg = logits_ref[...].astype(jnp.float32) / temperature
    col = k * bv + jax.lax.broadcasted_iota(jnp.int32, (br, bv), 1)
    valid = col < valid_v
    lg = jnp.where(valid, lg, NEG)

    # -- in-kernel Gumbel noise (same two paths as ws_step) ----------------
    if use_hw_prng:
        # step j has its own key words; (row block, vocab tile) fold in
        bits = hw_prng_bits(seed_ref[j, 0], seed_ref[j, 1], i, k, (br, bv))
    else:
        sl = seed_ref[pl.ds(j, 1)]                  # (1, BR, 2)
        k0 = sl[0, :, 0:1].astype(jnp.uint32)       # (BR, 1) per-row key
        k1 = sl[0, :, 1:2].astype(jnp.uint32)
        c0 = jnp.broadcast_to(ctr_ref[...], (br, bv)).astype(jnp.uint32)
        bits, _ = threefry2x32(k0, k1, c0, col.astype(jnp.uint32))
    g = gumbel_from_bits(bits)

    x = xs_ref[...]                     # (BR, 1) carried token state
    isx = col == x                      # (BR, BV)

    xlg_ref[...] += jnp.sum(jnp.where(isx, lg, 0.0), axis=1, keepdims=True)
    xg_ref[...] += jnp.sum(jnp.where(isx, g, 0.0), axis=1, keepdims=True)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(lg, axis=1, keepdims=True))
    s_ref[...] = (
        s_ref[...] * jnp.exp(m_prev - m_new)
        + jnp.sum(jnp.exp(lg - m_new), axis=1, keepdims=True)
    )
    m_ref[...] = m_new

    cand = jnp.where(isx | jnp.logical_not(valid), NEG, lg + g)
    tile_best = jnp.max(cand, axis=1, keepdims=True)
    tile_arg = k * bv + jnp.argmax(cand, axis=1).astype(jnp.int32)[:, None]
    better = tile_best > best_ref[...]
    bidx_ref[...] = jnp.where(better, tile_arg, bidx_ref[...])
    best_ref[...] = jnp.maximum(best_ref[...], tile_best)

    @pl.when(k == nvt - 1)
    def _finalize_step():
        ab = a_ref[pl.ds(j, 1)]                     # (1, BR, 1)
        a = ab[0]
        m = m_ref[...]
        s = s_ref[...]
        log_s = jnp.log(s)
        score_other = (
            jnp.log(jnp.maximum(a, MIN_PROB)) + best_ref[...] - m - log_s
        )
        p1x = jnp.exp(xlg_ref[...] - m) / s
        px = (1.0 - a) + a * p1x
        score_x = jnp.log(jnp.maximum(px, MIN_PROB)) + xg_ref[...]
        new_x = jnp.where(
            score_x >= score_other, x, bidx_ref[...]
        ).astype(jnp.int32)
        xs_ref[...] = new_x

        @pl.when(j == num_steps - 1)
        def _write_out():
            out_ref[...] = new_x


def ws_fused_streamed_pallas(
    logits: jax.Array,      # (R, Vp) — V padded to a multiple of vocab_tile
    x_t: jax.Array,         # (R, 1) int32
    a: jax.Array,           # (K, R, 1) float32 per-step mixing weights
    seeds: jax.Array,       # (K, R, 2) int32 (threefry) or (K, 2) (hw PRNG)
    ctr: jax.Array,         # (R, 1) int32 per-row noise counter word
    *,
    valid_v: int,
    row_block: int,
    vocab_tile: int,
    temperature: float = 1.0,
    use_hw_prng: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """K fused warm-start Euler steps over a 3-D (rows, K, vocab) grid."""
    r, vp = logits.shape
    num_steps = a.shape[0]
    assert r % row_block == 0, (r, row_block)
    assert vp % vocab_tile == 0, (vp, vocab_tile)
    nvt = vp // vocab_tile
    grid = (r // row_block, num_steps, nvt)
    kernel = functools.partial(
        _ws_fused_kernel,
        temperature=temperature, valid_v=valid_v, num_steps=num_steps,
        nvt=nvt, use_hw_prng=use_hw_prng,
    )
    if use_hw_prng:
        assert seeds.shape == (num_steps, 2), seeds.shape
        seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    else:
        assert seeds.shape == (num_steps, r, 2), seeds.shape
        seed_spec = pl.BlockSpec(
            (num_steps, row_block, 2), lambda i, j, k: (0, i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seed_spec,
            pl.BlockSpec((row_block, vocab_tile), lambda i, j, k: (i, k)),
            pl.BlockSpec((row_block, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((num_steps, row_block, 1), lambda i, j, k: (0, i, 0)),
            pl.BlockSpec((row_block, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((row_block, 1), lambda i, j, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((row_block, 1), jnp.int32),     # carried tokens
            pltpu.VMEM((row_block, 1), jnp.float32),   # m
            pltpu.VMEM((row_block, 1), jnp.float32),   # s
            pltpu.VMEM((row_block, 1), jnp.float32),   # best
            pltpu.VMEM((row_block, 1), jnp.int32),     # best idx
            pltpu.VMEM((row_block, 1), jnp.float32),   # lg at x
            pltpu.VMEM((row_block, 1), jnp.float32),   # gumbel at x
        ],
        interpret=interpret,
    )(jnp.asarray(seeds, jnp.int32), logits, x_t, a, ctr)
