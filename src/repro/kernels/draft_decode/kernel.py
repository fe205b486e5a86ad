"""Pallas decode-step kernels with a FIXED per-token reduction order.

Why this exists: the AR draft engine's bit-exactness contract
(drafting/ar_engine.py) requires batched prefill to reproduce the
scan-prefill token stream *bitwise*. Under plain XLA that fails — a
(B, S, D) matmul/layernorm/softmax tiles its reductions differently at
S=1 (decode) and S=P (prefill), drifting ~1e-6 in the logits and
eventually flipping a sampled token. These kernels pin the reduction
order by construction: every grid program takes a block of ROWS = 8
token rows at the SAME block shapes regardless of how many tokens share
the dispatch (the token rows are zero-padded to a multiple of ROWS), so
the only thing that changes between decode and prefill is the grid
size — never the shape (and therefore never the reduction order) of any
dot, norm or softmax. Every reduction runs along one row, so a row's
values do not depend on the other rows of its block. Eight rows is the
smallest block a TPU kernel takes (one f32 sublane tile).

Four kernels cover every reduction in the draft transformer forward:

  ``_qkv_rope_kernel``   ln1 -> q/k/v projections -> RoPE (grid over
                         blocks of the flattened B*S tokens).
  ``_attn_kernel``       ROWS query tokens of one batch row against the
                         FULL (T = max_len) KV cache buffer — the cache
                         length is static, so the softmax/PV reductions
                         run over the same T lanes in decode and prefill;
                         masking handles causality and cache validity.
  ``_post_attn_kernel``  wo projection + residual + ln2 + MLP + residual.
  ``_head_kernel``       final norm + vocab projection.

Everything *between* kernels is exact data movement (embedding gather,
``dynamic_update_slice`` cache writes, reshapes, transposes, padding)
which cannot change
values. See ops.py for the dispatcher and the supported-config gate.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38  # matches models/attention.py's mask constant
# token rows per grid program: one f32 sublane tile, the smallest row
# block a TPU kernel may take
ROWS = 8


def _norm_row(x, scale, bias, *, kind: str, eps: float):
    """Row norm at fixed (ROWS, D) shape; mirrors models/common.py formulas."""
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return y * (1.0 + scale.astype(jnp.float32))


def _act(name: str, x):
    if name == "silu":
        return jax.nn.silu(x)
    if name == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu(x)
    raise ValueError(name)


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def _rope_rows(x, pos, freq, *, head_dim: int):
    """RoPE on a block of token rows, lane-wise.

    x (rows, heads*head_dim); pos (rows, 1) f32; freq (1, heads*head_dim)
    holds each lane's frequency. Lane ``l`` of a head's first half pairs
    with ``l + half`` and lane ``l`` of its second half with ``l - half``,
    so ``x * cos + partner * sin`` is ``[x1 cos - x2 sin, x2 cos + x1 sin]``
    with no reshape.
    """
    half = head_dim // 2
    ang = pos * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    first = lane % head_dim < half
    partner = jnp.where(first, -jnp.roll(x, -half, axis=1),
                        jnp.roll(x, half, axis=1))
    return x * cos + partner * sin


def _qkv_rope_kernel(
    x_ref,        # (ROWS, D)
    pos_ref,      # (ROWS, 1) int32 — absolute position of each token
    lns_ref,      # (1, D) ln1 scale
    lnb_ref,      # (1, D) ln1 bias (zeros for rmsnorm)
    wq_ref,       # (D, H*hd)
    wk_ref,       # (D, KH*hd)
    wv_ref,       # (D, KH*hd)
    bq_ref, bk_ref, bv_ref,   # (1, *) biases (zeros when use_bias=False)
    fq_ref, fk_ref,           # (1, H*hd) / (1, KH*hd) per-lane RoPE freqs
    q_ref, k_ref, v_ref,      # outputs (ROWS, H*hd) / (ROWS, KH*hd) x2
    *,
    norm: str, eps: float, use_bias: bool, use_rope: bool, head_dim: int,
):
    h = _norm_row(x_ref[...], lns_ref[...], lnb_ref[...], kind=norm, eps=eps)
    q = _dot(h, wq_ref[...].astype(jnp.float32))
    k = _dot(h, wk_ref[...].astype(jnp.float32))
    v = _dot(h, wv_ref[...].astype(jnp.float32))
    if use_bias:
        q = q + bq_ref[...].astype(jnp.float32)
        k = k + bk_ref[...].astype(jnp.float32)
        v = v + bv_ref[...].astype(jnp.float32)
    if use_rope:
        pos = pos_ref[...].astype(jnp.float32)
        q = _rope_rows(q, pos, fq_ref[...], head_dim=head_dim)
        k = _rope_rows(k, pos, fk_ref[...], head_dim=head_dim)
    q_ref[...] = q
    k_ref[...] = k
    v_ref[...] = v


def _attn_kernel(
    q_ref,        # (1, H, ROWS, hd) — ROWS query tokens of one batch row
    k_ref,        # (1, KH, T, hd) — the row's FULL cache buffer
    v_ref,        # (1, KH, T, hd)
    sc_ref,       # SMEM (2,) int32 — [position of token 0, cache end]
    out_ref,      # (1, H, ROWS, hd)
    *,
    heads: int, kv_heads: int, head_dim: int,
):
    g = heads // kv_heads
    rows, t = q_ref.shape[2], k_ref.shape[2]
    scale = 1.0 / math.sqrt(head_dim)
    pos = (sc_ref[0] + pl.program_id(1) * rows
           + jax.lax.broadcasted_iota(jnp.int32, (rows, t), 0))
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
    valid = (col <= pos) & (col < sc_ref[1])

    for h in range(heads):
        q = q_ref[0, h].astype(jnp.float32)                # (ROWS, hd)
        k = k_ref[0, h // g].astype(jnp.float32)           # (T, hd)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (ROWS, T)
        sc = jnp.where(valid, sc, NEG_INF)
        m = jnp.max(sc, axis=-1, keepdims=True)
        p = jnp.exp(sc - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        out_ref[0, h] = _dot(p, v_ref[0, h // g].astype(jnp.float32)) / l


def _post_attn_kernel(
    a_ref,        # (ROWS, H*hd) — attention output of each token
    x_ref,        # (ROWS, D) — residual stream input
    wo_ref, bo_ref,           # (H*hd, D), (1, D)
    lns_ref, lnb_ref,         # ln2 scale/bias
    wup_ref, bup_ref,         # (D, F), (1, F)
    wgate_ref, bgate_ref,     # (D, F), (1, F) (zeros when ungated)
    wdown_ref, bdown_ref,     # (F, D), (1, D)
    out_ref,      # (ROWS, D)
    *,
    norm: str, eps: float, use_bias: bool, act: str, gated: bool,
):
    h = _dot(a_ref[...].astype(jnp.float32), wo_ref[...].astype(jnp.float32))
    if use_bias:
        h = h + bo_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32) + h
    hn = _norm_row(x, lns_ref[...], lnb_ref[...], kind=norm, eps=eps)
    up = _dot(hn, wup_ref[...].astype(jnp.float32))
    if use_bias:
        up = up + bup_ref[...].astype(jnp.float32)
    if gated:
        gate = _dot(hn, wgate_ref[...].astype(jnp.float32))
        if use_bias:
            gate = gate + bgate_ref[...].astype(jnp.float32)
        up = _act(act, gate) * up
    else:
        up = _act(act, up)
    down = _dot(up, wdown_ref[...].astype(jnp.float32))
    if use_bias:
        down = down + bdown_ref[...].astype(jnp.float32)
    out_ref[...] = x + down


def _head_kernel(
    x_ref,        # (ROWS, D)
    lns_ref, lnb_ref,         # final norm scale/bias
    w_ref,        # (D, V) — the head matrix (embed table pre-transposed
                  #          host-side when tie_embeddings)
    out_ref,      # (ROWS, V)
    *,
    norm: str, eps: float,
):
    h = _norm_row(x_ref[...], lns_ref[...], lnb_ref[...], kind=norm, eps=eps)
    out_ref[...] = _dot(h, w_ref[...].astype(jnp.float32))


# ---------------------------------------------------------------------------
# pallas_call wrappers (grid over ROWS-token blocks; weights are whole-array
# blocks). Callers pad the token rows to a multiple of ROWS.
# ---------------------------------------------------------------------------

def _rows_spec(width: int):
    return pl.BlockSpec((ROWS, width), lambda i: (i, 0))


def _full2(a):
    return pl.BlockSpec(a.shape, lambda i: (0, 0))


def pad_rows(a, rows: int):
    """Zero-pad the leading axis of ``a`` to ``rows``."""
    extra = rows - a.shape[0]
    return a if extra == 0 else jnp.pad(a, ((0, extra),) + ((0, 0),) * (a.ndim - 1))


def rope_lane_freqs(heads: int, head_dim: int, theta: float):
    """(1, heads*head_dim) frequency of each lane, as models/rope.py."""
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    return jnp.tile(freq, 2 * heads).reshape(1, heads * head_dim)


def qkv_rope_pallas(x, pos_r, ln, attn_p, *, norm, eps, use_bias, use_rope,
                    theta, heads, kv_heads, head_dim, interpret):
    """x (R, D); pos_r (R, 1) int32; R % ROWS == 0 ->
    (q (R, H*hd), k, v (R, KH*hd))."""
    r, d = x.shape
    qd, kd = heads * head_dim, kv_heads * head_dim
    lns = ln["scale"].reshape(1, d)
    lnb = (ln["bias"] if "bias" in ln else jnp.zeros_like(ln["scale"])
           ).reshape(1, d)
    zq, zk = jnp.zeros((1, qd), jnp.float32), jnp.zeros((1, kd), jnp.float32)
    bq = attn_p["wq"].get("b", zq[0]).reshape(1, qd)
    bk = attn_p["wk"].get("b", zk[0]).reshape(1, kd)
    bv = attn_p["wv"].get("b", zk[0]).reshape(1, kd)
    fq = rope_lane_freqs(heads, head_dim, theta)
    fk = rope_lane_freqs(kv_heads, head_dim, theta)
    kernel = functools.partial(
        _qkv_rope_kernel, norm=norm, eps=eps, use_bias=use_bias,
        use_rope=use_rope, head_dim=head_dim)
    args = (x, pos_r, lns, lnb, attn_p["wq"]["w"], attn_p["wk"]["w"],
            attn_p["wv"]["w"], bq, bk, bv, fq, fk)
    in_specs = [_rows_spec(d), _rows_spec(1)] + [_full2(a) for a in args[2:]]
    out_specs = (_rows_spec(qd), _rows_spec(kd), _rows_spec(kd))
    out_shape = (
        jax.ShapeDtypeStruct((r, qd), jnp.float32),
        jax.ShapeDtypeStruct((r, kd), jnp.float32),
        jax.ShapeDtypeStruct((r, kd), jnp.float32),
    )
    return pl.pallas_call(kernel, grid=(r // ROWS,), in_specs=in_specs,
                          out_specs=out_specs, out_shape=out_shape,
                          interpret=interpret)(*args)


def attn_cached_pallas(q, kbuf, vbuf, pos0, end, *, heads, kv_heads,
                       head_dim, interpret):
    """q (B, S, H*hd); kbuf/vbuf (B, T, KH*hd); pos0 / end int32 scalars.

    Each grid program takes ROWS query tokens of one batch row against
    that row's full T-length cache, so the reduction order over keys is
    the same for decode (S=1) and batched prefill (S=P). The head-major
    transposes around the call are data movement.
    """
    b, s, _ = q.shape
    t = kbuf.shape[1]
    sp = -(-s // ROWS) * ROWS
    qh = q.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)
    qh = jnp.pad(qh, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    kh = kbuf.reshape(b, t, kv_heads, head_dim).transpose(0, 2, 1, 3)
    vh = vbuf.reshape(b, t, kv_heads, head_dim).transpose(0, 2, 1, 3)
    sc = jnp.stack([jnp.asarray(pos0, jnp.int32), jnp.asarray(end, jnp.int32)])
    kernel = functools.partial(_attn_kernel, heads=heads, kv_heads=kv_heads,
                               head_dim=head_dim)
    q_spec = pl.BlockSpec((1, heads, ROWS, head_dim), lambda i, j: (i, 0, j, 0))
    kv_spec = pl.BlockSpec((1, kv_heads, t, head_dim),
                           lambda i, j: (i, 0, 0, 0))
    out = pl.pallas_call(
        kernel, grid=(b, sp // ROWS),
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, heads, sp, head_dim), jnp.float32),
        interpret=interpret)(qh, kh, vh, sc)
    return out[:, :, :s].transpose(0, 2, 1, 3).reshape(b, s, heads * head_dim)


def post_attn_pallas(a, x, attn_p, ln, mlp_p, *, norm, eps, use_bias, act,
                     interpret):
    """a (R, H*hd) attention out; x (R, D) residual; R % ROWS == 0 ->
    (R, D)."""
    r, d = x.shape
    qd = a.shape[1]
    f = mlp_p["up"]["w"].shape[1]
    gated = "gate" in mlp_p
    lns = ln["scale"].reshape(1, d)
    lnb = (ln["bias"] if "bias" in ln else jnp.zeros_like(ln["scale"])
           ).reshape(1, d)
    zd = jnp.zeros((1, d), jnp.float32)
    zf = jnp.zeros((1, f), jnp.float32)
    bo = attn_p["wo"].get("b", zd[0]).reshape(1, d)
    bup = mlp_p["up"].get("b", zf[0]).reshape(1, f)
    wgate = mlp_p["gate"]["w"] if gated else jnp.zeros((d, f), jnp.float32)
    bgate = (mlp_p["gate"].get("b", zf[0]) if gated else zf[0]).reshape(1, f)
    bdown = mlp_p["down"].get("b", zd[0]).reshape(1, d)
    kernel = functools.partial(_post_attn_kernel, norm=norm, eps=eps,
                               use_bias=use_bias, act=act, gated=gated)
    args = (a, x, attn_p["wo"]["w"], bo, lns, lnb, mlp_p["up"]["w"], bup,
            wgate, bgate, mlp_p["down"]["w"], bdown)
    in_specs = [_rows_spec(qd), _rows_spec(d)] + [_full2(w) for w in args[2:]]
    return pl.pallas_call(
        kernel, grid=(r // ROWS,), in_specs=in_specs,
        out_specs=_rows_spec(d),
        out_shape=jax.ShapeDtypeStruct((r, d), jnp.float32),
        interpret=interpret)(*args)


def head_pallas(x, fn, w, *, norm, eps, interpret):
    """x (R, D); w (D, V); R % ROWS == 0 -> logits (R, V)."""
    r, d = x.shape
    v = w.shape[1]
    lns = fn["scale"].reshape(1, d)
    lnb = (fn["bias"] if "bias" in fn else jnp.zeros_like(fn["scale"])
           ).reshape(1, d)
    kernel = functools.partial(_head_kernel, norm=norm, eps=eps)
    return pl.pallas_call(
        kernel, grid=(r // ROWS,),
        in_specs=[_rows_spec(d), _full2(lns), _full2(lnb), _full2(w)],
        out_specs=_rows_spec(v),
        out_shape=jax.ShapeDtypeStruct((r, v), jnp.float32),
        interpret=interpret)(x, lns, lnb, w)
