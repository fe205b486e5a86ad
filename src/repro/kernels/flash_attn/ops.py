"""Jit'd wrapper for the Pallas flash attention kernel: the model's
(B, S, H, D) arrays viewed as (B, S, H*D), seq padding to block
multiples, block sizes from the shape, and the interpret switch (CPU
validation vs TPU execution).

``interpret=None`` (default) goes through the central
``kernels.resolve_interpret``: compiled on a real TPU backend, interpret
elsewhere — the old hardcoded ``interpret=True`` default silently ran
the interpreter on TPU."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attn.kernel import (
    LANE, flash_attention_pallas, heads_per_block, pick_blocks,
)


def _padded(n: int, rows: int) -> int:
    """A length padded to the sublane tile, or to the lane tile past it."""
    step = rows if n <= LANE else LANE
    return -(-n // step) * step


def flash_attention(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, T, KH, D)
    v: jax.Array,            # (B, T, KH, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    mxu_dtype=None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Attention of q over k, v; query head ``h`` reads KV head
    ``h // (H // KH)``. Blocks default to ``pick_blocks`` of the padded
    shape (the whole key range in one block where VMEM holds it). Grouped
    heads of fewer than 128 lanes (no served model has them) repeat K and
    V: a lane block holds its own KV heads."""
    interpret = resolve_interpret(interpret)
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    hb = heads_per_block(h, d)
    if hb > 1 and kh != h:
        k, v = (jnp.repeat(a, h // kh, axis=2) for a in (k, v))
        kh = h
    if block_q is None or block_k is None:
        rows = 8 * max(1, 4 // q.dtype.itemsize)
        sp, tp = _padded(s, rows), _padded(t, rows)
        auto_q, auto_k = pick_blocks(sp, tp, hb * d, q.dtype.itemsize, hb)
        bq, bk = block_q or auto_q, block_k or auto_k
    else:
        bq = min(block_q, max(8, s))
        bk = min(block_k, max(8, t))
    sp = -(-s // bq) * bq
    tp = -(-t // bk) * bk

    qf = jnp.pad(q.reshape(b, s, h * d), ((0, 0), (0, sp - s), (0, 0)))
    kf = jnp.pad(k.reshape(b, t, kh * d), ((0, 0), (0, tp - t), (0, 0)))
    vf = jnp.pad(v.reshape(b, t, kh * d), ((0, 0), (0, tp - t), (0, 0)))
    out = flash_attention_pallas(
        qf, kf, vf, heads=h, kv_heads=kh, causal=causal, window=window,
        scale=scale, block_q=bq, block_k=bk, seq_k=t, mxu_dtype=mxu_dtype,
        interpret=interpret,
    )
    return out[:, :s].reshape(b, s, h, d)
