"""Pallas TPU blockwise flash attention (online softmax), with causal /
bidirectional / sliding-window masking.

Tiling: grid = (B*H, num_q_blocks, num_k_blocks); the k-axis is the
innermost ("arbitrary") dimension and accumulates into VMEM scratch
(running max m, normaliser l, and the (BQ, D) output accumulator). Q/K
blocks are MXU-aligned (default 128x128); D rides along whole (<= 256).

Out-of-range K blocks (fully masked under causal/window) are skipped with
pl.when — the same effect as splash attention's block sparsity for the
sliding-window layers (Gemma3 locals, long-context variant).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, block_q: int, block_k: int,
               causal: bool, window: Optional[int],
               seq_q: int, seq_k: int, num_k_blocks: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

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
        q = q_ref[0].astype(jnp.float32)              # (BQ, D)
        k = k_ref[0].astype(jnp.float32)              # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # (BQ, BK)

        qi = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = (qi < seq_q) & (ki < seq_k)
        if causal:
            mask &= ki <= qi
            if window is not None:
                mask &= ki > qi - window
        elif window is not None:
            mask &= jnp.abs(ki - qi) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                            # (BQ, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # (BQ, BK)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(kb == num_k_blocks - 1)
    def finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,            # (BH, Sq, D) — heads folded into batch
    k: jax.Array,            # (BH, Sk, D)
    v: jax.Array,            # (BH, Sk, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    seq_q: Optional[int] = None,
    seq_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    seq_q = seq_q if seq_q is not None else sq
    seq_k = seq_k if seq_k is not None else sk
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    nq, nk = sq // block_q, sk // block_k

    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, seq_q=seq_q, seq_k=seq_k,
        num_k_blocks=nk,
    )
    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            # (m, l, acc) online-softmax accumulators in VMEM scratch
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(q, k, v)
