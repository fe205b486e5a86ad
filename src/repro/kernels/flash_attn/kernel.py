"""Pallas TPU flash attention (online softmax), with causal /
bidirectional / sliding-window masking and grouped-query heads.

Layout: q (B, Sq, H*D), k and v (B, Sk, KH*D): the model's own layout,
heads side by side in the lane dimension, so no transpose runs around
the kernel and no head of D < 128 lanes is padded to 128. A grid step
takes a lane block of ``hb`` heads (``heads_per_block``): one head where
D is a multiple of 128, 128 // D heads where D divides 128 (DFM-DiT's
D = 64: two), else all H. Head t of a block is computed on the whole
block with the other heads' lanes of q zeroed: on the 128-deep MXU a
64-deep contraction costs the same pass, and the zeros add nothing.

Grouped KV heads (hb = 1): query head ``h`` reads KV lane block
``h // (H // KH)`` through the K/V index map, so K and V are never
repeated in HBM; consecutive query heads of one group map to the same
block, whose copy the pipeline skips. With hb > 1 every block holds its
own KV heads (KH = H).

Two grids, chosen by ``block_k``:

* ``block_k == Sk`` (one key block): grid (B, H // hb, Sq // block_q).
  Each step holds a head's whole score row block (block_q, Sk) in VMEM,
  takes its max, exp and sum, normalises and multiplies by V. No running
  max, no rescaling, no scratch. This is the served path.
* ``block_k < Sk``: grid (B, H // hb, Sq // block_q, Sk // block_k); the
  key axis is innermost ("arbitrary") and accumulates the running max m,
  sum l and the (block_q, hb * D) output in VMEM scratch. Key blocks
  wholly outside the causal / window band are skipped with ``pl.when``.

Either way the (Sq, Sk) score tensor never reaches HBM.

Arithmetic: scores, softmax statistics and accumulators are float32.
Matmul operands are cast to ``mxu_dtype`` (default: the inputs' dtype)
and accumulate in float32; bf16 inputs stay bf16. A float32 caller that
wants what an XLA einsum at default precision does on a TPU (one bf16
pass, float32 accumulation) passes ``mxu_dtype=bfloat16``.

VMEM arithmetic (``vmem_bytes``; v5e has 128 MiB of VMEM per core, and
``pick_blocks`` keeps a kernel's working set under ``VMEM_BUDGET``), with
L = hb * D lanes padded to 128:

* pipelined blocks: q, out (block_q rows) and k, v (block_k rows), each
  double-buffered: ``2 * (2 * block_q + 2 * block_k) * L * itemsize``;
* one head's score tile and its float32 temporaries (scores, exp,
  normalised probabilities, the MXU operand copy): ``4 * block_q *
  block_k * 4``;
* with several key blocks, scratch m, l (per head, lane-padded) and the
  output accumulator: ``block_q * (L + 2 * hb * 128) * 4``.

DFM-DiT's 1024-token bucket (H = KH = 12, D = 64, hb = 2, float32): one
key block of 1024 and block_q = 1024 gives 4 MiB of blocks + 16 MiB of
tile = 20 MiB, grid (B, 6, 1). StarCoder2-3B's 512 bucket (H = 24,
KH = 2, D = 128, hb = 1, bf16): 1 MiB + 4 MiB, grid (B, 24, 1).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
LANE = 128
VMEM_BUDGET = 24 * 2**20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def heads_per_block(h: int, d: int) -> int:
    """Heads in one lane block: one where D fills whole lane tiles, the
    heads of one 128-lane tile where D divides 128, else all of them."""
    if d % LANE == 0:
        return 1
    if LANE % d == 0 and h % (LANE // d) == 0:
        return LANE // d
    return h


def vmem_bytes(block_q: int, block_k: int, lanes: int, itemsize: int,
               one_block: bool, hb: int = 1) -> int:
    """The kernel's VMEM working set (module docstring); ``lanes`` = hb*D."""
    lanes = _round_up(lanes, LANE)
    blocks = 2 * (2 * block_q + 2 * block_k) * lanes * itemsize
    tile = 4 * block_q * block_k * 4
    scratch = 0 if one_block else block_q * (lanes + 2 * hb * LANE) * 4
    return blocks + tile + scratch


def _divisors(n: int, step: int):
    """Multiples of ``step`` that divide ``n``, largest first; ``n``
    itself always leads."""
    return [n] + [c for c in range(n - n % step, step - 1, -step)
                  if c < n and n % c == 0]


def pick_blocks(sq: int, sk: int, lanes: int, itemsize: int, hb: int = 1,
                budget: int = VMEM_BUDGET):
    """(block_q, block_k) for padded lengths ``sq`` and ``sk``: the whole
    key range in one block where it fits beside the smallest query
    block, else the largest 128-multiple that divides ``sk`` and fits;
    then the largest query block that fits with it."""
    q_min = min(sq, LANE)
    if vmem_bytes(q_min, sk, lanes, itemsize, True, hb) <= budget:
        bk = sk
    else:
        bk = next((c for c in _divisors(sk, LANE)[1:]
                   if vmem_bytes(q_min, c, lanes, itemsize, False, hb)
                   <= budget), min(sk, LANE))
    rows = 8 * max(1, 4 // itemsize)                  # sublane tile
    bq = next((c for c in _divisors(sq, rows)
               if vmem_bytes(c, bk, lanes, itemsize, bk == sk, hb) <= budget),
              q_min)
    return bq, bk


def _mask(s, q_start, k_start, *, causal, window, seq_k):
    """Apply the causal / window / key-padding mask to a score tile, or
    return it as is when nothing is masked."""
    if not (causal or window is not None or seq_k is not None):
        return s
    qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, bool)
    if seq_k is not None:
        mask &= ki < seq_k
    if causal:
        mask &= ki <= qi
        if window is not None:
            mask &= ki > qi - window
    elif window is not None:
        mask &= jnp.abs(ki - qi) < window
    return jnp.where(mask, s, NEG_INF)


def _heads(shape, hb, d):
    """Per head of a block: its lane mask (None for a one-head block)."""
    if hb == 1:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return [(lane >= t * d) & (lane < (t + 1) * d) for t in range(hb)]


def _scores(q, k, lanes, scale):
    """One head's (BQ, BK) scores: q with the other heads' lanes zeroed."""
    if lanes is not None:
        q = jnp.where(lanes, q, jnp.zeros_like(q))
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale


def _pv(p, v, mxu_dtype):
    return jax.lax.dot_general(
        p.astype(mxu_dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _one_block_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, block_q, hb, d,
                      causal, window, seq_k, mxu_dtype):
    """Softmax over the whole key range at once: normalise, then PV, as
    ``jax.nn.softmax`` followed by the PV einsum does."""
    q = q_ref[0].astype(mxu_dtype)                    # (BQ, L)
    k = k_ref[0].astype(mxu_dtype)                    # (BK, L)
    v = v_ref[0].astype(mxu_dtype)
    out = None
    for lanes in _heads(q.shape, hb, d):
        s = _scores(q, k, lanes, scale)
        s = _mask(s, pl.program_id(2) * block_q, 0, causal=causal,
                  window=window, seq_k=seq_k)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        p = p * (1.0 / jnp.sum(p, axis=-1, keepdims=True))
        o = _pv(p, v, mxu_dtype)                      # (BQ, L)
        out = o if lanes is None or out is None else jnp.where(lanes, o, out)
    o_ref[0] = out.astype(o_ref.dtype)


def _online_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale, block_q, block_k, hb, d, causal, window, seq_k,
                   num_k_blocks, mxu_dtype):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qb * block_q
    k_start = kb * block_k

    # block-level skip: causal => skip blocks entirely above the diagonal;
    # window => also skip blocks entirely below the band.
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
        if window is not None:
            run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)
    elif window is not None:
        run = jnp.logical_and(
            k_start + block_k - 1 > q_start - window,
            k_start < q_start + block_q + window,
        )

    @pl.when(run)
    def body():
        q = q_ref[0].astype(mxu_dtype)
        k = k_ref[0].astype(mxu_dtype)
        v = v_ref[0].astype(mxu_dtype)
        acc = acc_ref[...]                             # (BQ, L)
        for t, lanes in enumerate(_heads(q.shape, hb, d)):
            s = _scores(q, k, lanes, scale)
            s = _mask(s, q_start, k_start, causal=causal, window=window,
                      seq_k=seq_k)
            m_prev = m_ref[t]                          # (BQ, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                     # (BQ, BK)
            l_ref[t] = l_ref[t] * alpha + jnp.sum(p, -1, keepdims=True)
            m_ref[t] = m_new
            new = acc * alpha + _pv(p, v, mxu_dtype)
            acc = new if lanes is None else jnp.where(lanes, new, acc)
        acc_ref[...] = acc

    @pl.when(kb == num_k_blocks - 1)
    def finalize():
        acc = acc_ref[...]
        out = None
        for t, lanes in enumerate(_heads(acc.shape, hb, d)):
            o = acc / jnp.maximum(l_ref[t], 1e-30)
            out = o if lanes is None or out is None else jnp.where(lanes, o, out)
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,            # (B, Sq, H*D)
    k: jax.Array,            # (B, Sk, KH*D)
    v: jax.Array,            # (B, Sk, KH*D)
    *,
    heads: int,
    kv_heads: int,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int,
    block_k: int,
    seq_k: Optional[int] = None,
    mxu_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``seq_k``: the number of real keys where ``Sk`` is padded past it
    (None: every key is real). Heads of fewer than 128 lanes share a lane
    block only with their own KV heads: ``kv_heads == heads`` there."""
    b, sq, hd = q.shape
    sk = k.shape[1]
    d = hd // heads
    g = heads // kv_heads
    hb = heads_per_block(heads, d)
    assert heads % kv_heads == 0 and (hb == 1 or g == 1), (heads, kv_heads, d)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    mxu_dtype = jnp.dtype(mxu_dtype or q.dtype)
    nq, nk = sq // block_q, sk // block_k
    seq_k = seq_k if seq_k is not None and seq_k < sk else None
    one = nk == 1
    lanes = hb * d
    need = vmem_bytes(block_q, block_k, lanes, q.dtype.itemsize, one, hb)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3 + (() if one else ("arbitrary",)),
        vmem_limit_bytes=max(32 * 2**20, need + 8 * 2**20))
    common = dict(scale=scale, block_q=block_q, hb=hb, d=d, causal=causal,
                  window=window, seq_k=seq_k, mxu_dtype=mxu_dtype)
    if one:
        kernel = functools.partial(_one_block_kernel, **common)
        grid = (b, heads // hb, nq)
        q_map = lambda bi, j, i: (bi, i, j)
        kv_map = lambda bi, j, i: (bi, 0, j // g)
        scratch = []
    else:
        kernel = functools.partial(_online_kernel, block_k=block_k,
                                   num_k_blocks=nk, **common)
        grid = (b, heads // hb, nq, nk)
        q_map = lambda bi, j, i, kk: (bi, i, j)
        kv_map = lambda bi, j, i, kk: (bi, kk, j // g)
        # per head m, l and the (BQ, L) accumulator, in VMEM scratch
        scratch = [pltpu.VMEM((hb, block_q, 1), jnp.float32),
                   pltpu.VMEM((hb, block_q, 1), jnp.float32),
                   pltpu.VMEM((block_q, lanes), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, lanes), q_map),
            pl.BlockSpec((1, block_k, lanes), kv_map),
            pl.BlockSpec((1, block_k, lanes), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, lanes), q_map),
        out_shape=jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=params,
    )(q, k, v)
