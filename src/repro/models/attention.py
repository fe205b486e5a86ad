"""Attention blocks: GQA (full / sliding-window, causal / bidirectional,
optional qk-norm and logit softcap) and DeepSeek-style MLA (multi-head
latent attention with a compressed KV cache).

Masking semantics:
  mode="bidir"   — DFM denoiser (DiT-like) full visibility
  mode="causal"  — AR training / prefill
  decode         — single query against a cache of length `pos`

GQA's attention is picked per call by ``attention_impl`` from
``cfg.attn_impl``: ``"auto"`` (the default) runs the fused Pallas kernel
(kernels/flash_attn, one key block in VMEM, the score tensor never in
HBM) for the bidirectional denoiser on one TPU, and the XLA einsum path
``_sdpa`` everywhere else; ``"xla"`` forces ``_sdpa``; ``"chunked"``
takes ``_sdpa_chunked`` past ``cfg.attn_chunk``. The fused path is
checked against ``_sdpa`` in tests, and differentiates through it.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MLASettings
from repro.kernels import default_platform
from repro.kernels.flash_attn import flash_attention
from repro.models.common import (
    dense, dense_init, init_rmsnorm, rmsnorm, param_dtype,
)
from repro.models.rope import apply_rope

NEG_INF = -2.3819763e38  # matches XLA's mask constant for f32


# ---------------------------------------------------------------------------
# masks
# ---------------------------------------------------------------------------

def attn_mask(
    q_pos: jax.Array,          # (B, S) int32
    k_pos: jax.Array,          # (B, T) int32
    *,
    mode: str,                 # bidir | causal
    window: Optional[int],     # sliding window size (None = full)
    k_valid: Optional[jax.Array] = None,  # (B, T) bool — cache validity
) -> jax.Array:
    """Boolean (B, S, T) mask, True = attend."""
    q = q_pos[:, :, None]
    k = k_pos[:, None, :]
    m = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    if mode == "causal":
        m = m & (k <= q)
    if window is not None:
        m = m & (k > q - window) & (k <= q) if mode != "bidir" else m & (jnp.abs(k - q) < window)
    if k_valid is not None:
        m = m & k_valid[:, None, :]
    return m


def _sdpa(q, k, v, mask, *, scale, softcap=0.0):
    """q (B,S,KH,G,D), k (B,T,KH,D), v (B,T,KH,Dv), mask (B,S,T)."""
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out


def _mxu_dtype(dtype):
    """The operand dtype of ``_sdpa``'s einsums at default precision: on a
    TPU a float32 einsum is one bf16 pass with float32 accumulation."""
    if (dtype == jnp.float32 and default_platform() == "tpu"
            and jax.config.jax_default_matmul_precision in (
                None, "default", "bfloat16", "BF16_BF16_F32")):
        return jnp.bfloat16
    return dtype


def _sdpa_bidir(q, k, v, scale):
    """``_sdpa`` unmasked, on the fused path's layout: q (B,S,H,D), k and
    v (B,T,KH,D)."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    mask = jnp.ones((b, s, k.shape[1]), bool)
    out = _sdpa(q.reshape(b, s, kh, h // kh, d), k, v, mask, scale=scale)
    return out.reshape(b, s, h, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def fused_attention(q, k, v, scale, interpret=None):
    """Bidirectional attention in one Pallas kernel, arithmetic as
    ``_sdpa``'s (float32 scores and softmax, matmul operands as the einsum
    at default precision takes them); its VJP is ``_sdpa``'s, recomputed."""
    return flash_attention(q, k, v, causal=False, scale=scale,
                           mxu_dtype=_mxu_dtype(q.dtype), interpret=interpret)


def _fused_attention_fwd(q, k, v, scale, interpret):
    return fused_attention(q, k, v, scale, interpret), (q, k, v)


def _fused_attention_bwd(scale, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(_sdpa_bidir, scale=scale), *res)
    return vjp(g)


fused_attention.defvjp(_fused_attention_fwd, _fused_attention_bwd)


def _sdpa_chunked(q, k, v, q_pos, k_pos, *, scale, softcap=0.0,
                  mode="causal", window=None, k_valid=None,
                  chunk: int = 1024):
    """Flash-style chunked attention in pure XLA (lowerable on any backend):
    lax.scan over key chunks with an online-softmax carry, bounding the
    materialised score tensor to (B,KH,G,S,chunk) instead of (...,S,T).

    This is the XLA mirror of kernels/flash_attn — used by the dry-run and
    selectable via ModelConfig.attn_impl='chunked' (§Perf iteration).
    """
    b, s, kh, g, d = q.shape
    t = k.shape[1]
    nc = -(-t // chunk)
    pad = nc * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        if k_valid is not None:
            k_valid = jnp.pad(k_valid, ((0, 0), (0, pad)))
        else:
            k_valid = jnp.pad(jnp.ones((b, t), bool), ((0, 0), (0, pad)))
    elif k_valid is None:
        k_valid = jnp.ones((b, k.shape[1]), bool)

    kc = jnp.moveaxis(k.reshape(b, nc, chunk, kh, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, chunk, kh, d), 1, 0)
    kpc = jnp.moveaxis(k_pos.reshape(b, nc, chunk), 1, 0)
    kvc = jnp.moveaxis(k_valid.reshape(b, nc, chunk), 1, 0)

    from repro.distributed.sharding import constrain

    def pin(m_, l_, acc_):
        # pin carries head-sharded (see _mla_chunked; §Perf iteration 7)
        m_ = constrain(m_, ("batch", "kv_heads", None, None))
        l_ = constrain(l_, ("batch", "kv_heads", None, None))
        acc_ = constrain(acc_, ("batch", None, "kv_heads", None, None))
        return m_, l_, acc_

    m0 = jnp.full((b, kh, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, s), jnp.float32)
    acc0 = jnp.zeros((b, s, kh, g, d), jnp.float32)
    m0, l0, acc0 = pin(m0, l0, acc0)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kj, vj, kp, kvld = xs
        sc = jnp.einsum("bskgd,btkd->bkgst", q, kj).astype(jnp.float32) * scale
        if softcap > 0:
            sc = softcap * jnp.tanh(sc / softcap)
        msk = attn_mask(q_pos, kp, mode=mode, window=window, k_valid=kvld)
        sc = jnp.where(msk[:, None, None], sc, NEG_INF)
        m_cur = jnp.max(sc, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(p, -1)
        upd = jnp.einsum("bkgst,btkd->bskgd", p.astype(vj.dtype), vj)
        acc = acc * jnp.moveaxis(alpha, 3, 1)[..., None] + upd.astype(jnp.float32)
        m_new, l_new, acc = pin(m_new, l_new, acc)
        return (m_new, l_new, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, kpc, kvc))
    l = jnp.maximum(jnp.moveaxis(l, 3, 1), 1e-30)
    return (acc / l[..., None]).astype(v.dtype)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

_taken: contextvars.ContextVar = contextvars.ContextVar(
    "attention_taken", default=None)


@contextlib.contextmanager
def record_attention():
    """Yield a list that collects, while a program is traced inside the
    block, the attention (``attention_impl``'s answer) of each GQA call."""
    log: list = []
    token = _taken.set(log)
    try:
        yield log
    finally:
        _taken.reset(token)


def attention_impl(cfg: ModelConfig, *, mode: str, cached: bool,
                   window: Optional[int], seq: int,
                   backend: Optional[str] = None,
                   devices: Optional[int] = None) -> str:
    """``"fused"``, ``"chunked"`` or ``"xla"``: the attention a GQA call
    of ``seq`` queries takes. ``"auto"`` fuses where the kernel is the
    whole of the job: a TPU backend, one device (no mesh in scope),
    bidirectional, no cache, no softcap, no window narrower than ``seq``
    and ``seq`` a multiple of 128. ``backend`` and ``devices`` default to
    ``kernels.default_platform()`` and the size of the mesh in scope."""
    if cfg.attn_impl == "chunked":
        return "chunked" if seq > cfg.attn_chunk else "xla"
    if cfg.attn_impl != "auto":
        return "xla"
    if backend is None:
        backend = default_platform()
    if devices is None:
        from repro.distributed.sharding import current_mesh
        mesh = current_mesh()
        devices = 1 if mesh is None else mesh.size
    fused = (backend == "tpu" and devices == 1 and mode == "bidir"
             and not cached and not cfg.attn_logit_softcap
             and (window is None or window >= seq) and seq % 128 == 0)
    return "fused" if fused else "xla"


def init_gqa(key, cfg: ModelConfig) -> dict:
    pd = param_dtype(cfg)
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, cfg.num_heads * hd, pd, bias=cfg.use_bias),
        "wk": dense_init(ks[1], d, cfg.num_kv_heads * hd, pd, bias=cfg.use_bias),
        "wv": dense_init(ks[2], d, cfg.num_kv_heads * hd, pd, bias=cfg.use_bias),
        "wo": dense_init(ks[3], cfg.num_heads * hd, d, pd, bias=cfg.use_bias,
                         stddev=0.02 / math.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qk_norm:
        p["qnorm"] = init_rmsnorm(hd, pd)
        p["knorm"] = init_rmsnorm(hd, pd)
    return p


def gqa_attention(
    p: dict,
    x: jax.Array,                       # (B, S, D)
    cfg: ModelConfig,
    *,
    sin: jax.Array, cos: jax.Array,      # rope angles for the query positions
    mode: str = "causal",
    window: Optional[int] = None,
    q_pos: jax.Array,                    # (B, S)
    cache: Optional[dict] = None,        # {"k","v": (B,T,KH,D), "pos": ()} decode/prefill
    cache_sin: Optional[jax.Array] = None,  # rope angles already baked in cache
) -> Tuple[jax.Array, Optional[dict]]:
    b, s, _ = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kh
    q = dense(p["wq"], x).reshape(b, s, h, hd)
    k = dense(p["wk"], x).reshape(b, s, kh, hd)
    v = dense(p["wv"], x).reshape(b, s, kh, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["qnorm"], q, cfg.norm_eps)
        k = rmsnorm(p["knorm"], k, cfg.norm_eps)
    if sin is not None:
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    scale = 1.0 / math.sqrt(hd)

    impl = attention_impl(cfg, mode=mode, cached=cache is not None,
                          window=window, seq=s)
    log = _taken.get()
    if log is not None:
        log.append(impl)
    use_chunked = impl == "chunked"

    new_cache = None
    with jax.named_scope("attention"):
        if impl == "fused":
            out = fused_attention(q, k, v, scale)
        elif cache is not None:
            # write current k/v at positions q_pos into the cache buffer
            t = cache["k"].shape[1]
            start = cache["pos"]
            kbuf = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, start, 0, 0))
            vbuf = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, start, 0, 0))
            new_cache = {"k": kbuf, "v": vbuf, "pos": start + s}
            k_full, v_full = kbuf.astype(x.dtype), vbuf.astype(x.dtype)
            k_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
            k_valid = k_pos[0][None, :] < (start + s)
            qh = q.reshape(b, s, kh, g, hd)
            if use_chunked:
                out = _sdpa_chunked(qh, k_full, v_full, q_pos, k_pos,
                                    scale=scale,
                                    softcap=cfg.attn_logit_softcap,
                                    mode="causal", window=window,
                                    k_valid=k_valid, chunk=cfg.attn_chunk)
            else:
                mask = attn_mask(q_pos, k_pos, mode="causal", window=window,
                                 k_valid=k_valid)
                out = _sdpa(qh, k_full, v_full, mask, scale=scale,
                            softcap=cfg.attn_logit_softcap)
        else:
            k_pos = q_pos
            qh = q.reshape(b, s, kh, g, hd)
            if use_chunked:
                out = _sdpa_chunked(qh, k, v, q_pos, k_pos, scale=scale,
                                    softcap=cfg.attn_logit_softcap, mode=mode,
                                    window=window, chunk=cfg.attn_chunk)
            else:
                mask = attn_mask(q_pos, k_pos, mode=mode, window=window)
                out = _sdpa(qh, k, v, mask, scale=scale,
                            softcap=cfg.attn_logit_softcap)

    out = out.reshape(b, s, h * hd)
    return dense(p["wo"], out), new_cache


def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, max_len, kh, hd), dtype),
        "v": jnp.zeros((batch, max_len, kh, hd), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3, arXiv:2412.19437). Decode caches the compressed latent
# c_kv plus the shared rotary key — the whole point of MLA.
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig) -> dict:
    m: MLASettings = cfg.mla
    pd = param_dtype(cfg)
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    return {
        "wq_a": dense_init(ks[0], d, m.q_lora_rank, pd),
        "q_norm": init_rmsnorm(m.q_lora_rank, pd),
        "wq_b": dense_init(ks[1], m.q_lora_rank, h * qk, pd),
        "wkv_a": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, pd),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, pd),
        "wkv_b": dense_init(ks[3], m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), pd),
        "wo": dense_init(ks[4], h * m.v_head_dim, d, pd,
                         stddev=0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def mla_attention(
    p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    sin: jax.Array, cos: jax.Array,
    mode: str = "causal",
    window: Optional[int] = None,
    q_pos: jax.Array,
    cache: Optional[dict] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    m: MLASettings = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    nd, rd, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    # MLA's decoupled rotary dims differ from cfg.head_dim — derive angles
    # for qk_rope_head_dim directly from the query positions.
    from repro.models.rope import rope_angles
    sin, cos = rope_angles(q_pos, rd, cfg.rope_theta)

    q = dense(p["wq_b"], rmsnorm(p["q_norm"], dense(p["wq_a"], x), cfg.norm_eps))
    q = q.reshape(b, s, h, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, sin, cos)

    kv_a = dense(p["wkv_a"], x)                       # (B,S,r+rd)
    c_kv = rmsnorm(p["kv_norm"], kv_a[..., : m.kv_lora_rank], cfg.norm_eps)
    k_pe = apply_rope(kv_a[..., m.kv_lora_rank:][:, :, None, :], sin, cos)[:, :, 0]  # (B,S,rd)

    new_cache = None
    if cache is not None:
        t = cache["c_kv"].shape[1]
        start = cache["pos"]
        cbuf = jax.lax.dynamic_update_slice(cache["c_kv"], c_kv.astype(cache["c_kv"].dtype),
                                            (0, start, 0))
        pbuf = jax.lax.dynamic_update_slice(cache["k_pe"], k_pe.astype(cache["k_pe"].dtype),
                                            (0, start, 0))
        new_cache = {"c_kv": cbuf, "k_pe": pbuf, "pos": start + s}
        c_all, pe_all = cbuf.astype(x.dtype), pbuf.astype(x.dtype)
        k_pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        k_valid = k_pos[0][None, :] < (start + s)
        mask = attn_mask(q_pos, k_pos, mode="causal", window=window, k_valid=k_valid)
    else:
        c_all, pe_all = c_kv, k_pe
        k_pos = q_pos
        mask = attn_mask(q_pos, k_pos, mode=mode, window=window)

    scale = 1.0 / math.sqrt(nd + rd)
    if cfg.mla_absorb and cache is not None:
        # Absorbed MLA (DeepSeek-V2 inference trick, §Perf iteration):
        # attention runs directly in the compressed latent space — the
        # (S, H, nd+vd) per-head expansion of the whole cache is never
        # materialised. W_uk is folded into the query, W_uv into the
        # output: per step this reads the (S, r) latent once.
        w = p["wkv_b"]["w"].astype(x.dtype)              # (r, H*(nd+vd))
        w = w.reshape(m.kv_lora_rank, h, nd + vd)
        w_uk, w_uv = w[..., :nd], w[..., nd:]
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)     # (B,S,H,r)
        sc = jnp.einsum("bshr,btr->bhst", q_lat, c_all)
        sc = sc + jnp.einsum("bshd,btd->bhst", q_rope, pe_all)
        sc = sc.astype(jnp.float32) * scale
        sc = jnp.where(mask[:, None], sc, NEG_INF)
        probs = jax.nn.softmax(sc, axis=-1).astype(x.dtype)
        out_lat = jnp.einsum("bhst,btr->bshr", probs, c_all)    # (B,S,H,r)
        out = jnp.einsum("bshr,rhd->bshd", out_lat, w_uv)
        out = out.reshape(b, s, h * vd)
        return dense(p["wo"], out), new_cache

    if cfg.attn_impl == "chunked" and s > cfg.attn_chunk:
        # flash-style chunked MLA (§Perf): expand the latent to per-head
        # K/V one key-chunk at a time inside an online-softmax scan — the
        # (T, H, nd+vd) expansion and the (S, T) score tensor are never
        # materialised at full length.
        out = _mla_chunked(
            p, q_nope, q_rope, c_all, pe_all, cfg,
            q_pos=q_pos, k_pos=k_pos,
            k_valid=jnp.broadcast_to(
                k_pos[0][None, :] < (cache["pos"] + s), k_pos.shape
            ) if cache is not None else None,
            mode="causal" if cache is not None else mode,
            window=window, scale=scale, chunk=cfg.attn_chunk,
        )
        return dense(p["wo"], out.reshape(b, s, h * vd)), new_cache

    # naive expansion (baseline): per-head keys/values for all positions
    kv = dense(p["wkv_b"], c_all).reshape(b, -1, h, nd + vd)
    k_nope, v = kv[..., :nd], kv[..., nd:]

    sc = jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
    sc = sc + jnp.einsum("bshd,btd->bhst", q_rope, pe_all)
    sc = sc.astype(jnp.float32) * scale
    sc = jnp.where(mask[:, None], sc, NEG_INF)
    probs = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(b, s, h * vd)
    return dense(p["wo"], out), new_cache


def _mla_chunked(p, q_nope, q_rope, c_all, pe_all, cfg, *, q_pos, k_pos,
                 k_valid, mode, window, scale, chunk):
    m_set: MLASettings = cfg.mla
    b, s, h, nd = q_nope.shape
    vd = m_set.v_head_dim
    t = c_all.shape[1]
    nc = -(-t // chunk)
    pad = nc * chunk - t
    if pad:
        c_all = jnp.pad(c_all, ((0, 0), (0, pad), (0, 0)))
        pe_all = jnp.pad(pe_all, ((0, 0), (0, pad), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        k_valid = jnp.pad(
            k_valid if k_valid is not None else jnp.ones((b, t), bool),
            ((0, 0), (0, pad)))
    elif k_valid is None:
        k_valid = jnp.ones((b, t), bool)

    cc = jnp.moveaxis(c_all.reshape(b, nc, chunk, -1), 1, 0)
    pc = jnp.moveaxis(pe_all.reshape(b, nc, chunk, -1), 1, 0)
    kpc = jnp.moveaxis(k_pos.reshape(b, nc, chunk), 1, 0)
    kvc = jnp.moveaxis(k_valid.reshape(b, nc, chunk), 1, 0)

    from repro.distributed.sharding import constrain

    def pin(m_, l_, acc_):
        # pin the online-softmax carries to head-sharded layout — without
        # this GSPMD replicates the scan carry across `model` and inserts
        # a full-head all-gather per key chunk (measured 8 TB/step on
        # deepseek train_4k; §Perf iteration 7)
        m_ = constrain(m_, ("batch", "heads", None))
        l_ = constrain(l_, ("batch", "heads", None))
        acc_ = constrain(acc_, ("batch", None, "heads", None))
        return m_, l_, acc_

    m0 = jnp.full((b, h, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    acc0 = jnp.zeros((b, s, h, vd), jnp.float32)
    m0, l0, acc0 = pin(m0, l0, acc0)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        cj, pj, kp, kvld = xs
        kv = dense(p["wkv_b"], cj).reshape(b, chunk, h, nd + vd)
        k_nope, v = kv[..., :nd], kv[..., nd:]
        sc = jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
        sc = sc + jnp.einsum("bshd,btd->bhst", q_rope, pj)
        sc = sc.astype(jnp.float32) * scale
        msk = attn_mask(q_pos, kp, mode=mode, window=window, k_valid=kvld)
        sc = jnp.where(msk[:, None], sc, NEG_INF)
        m_cur = jnp.max(sc, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        prob = jnp.exp(sc - m_new[..., None])
        l_new = l_prev * alpha + jnp.sum(prob, -1)
        upd = jnp.einsum("bhst,bthd->bshd", prob.astype(v.dtype), v)
        # alpha (B,H,S) -> (B,S,H,1) to rescale the accumulator
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + upd.astype(jnp.float32)
        m_new, l_new, acc = pin(m_new, l_new, acc)
        return (m_new, l_new, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (cc, pc, kpc, kvc))
    l = jnp.maximum(l.transpose(0, 2, 1), 1e-30)
    return (acc / l[..., None]).astype(c_all.dtype)


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_pe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder): keys/values from encoder output,
# computed once at prefill and cached.
# ---------------------------------------------------------------------------

def init_cross_attn(key, cfg: ModelConfig) -> dict:
    pd = param_dtype(cfg)
    d, hd, h = cfg.d_model, cfg.head_dim, cfg.num_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * hd, pd, bias=cfg.use_bias),
        "wk": dense_init(ks[1], d, h * hd, pd, bias=cfg.use_bias),
        "wv": dense_init(ks[2], d, h * hd, pd, bias=cfg.use_bias),
        "wo": dense_init(ks[3], h * hd, d, pd, bias=cfg.use_bias,
                         stddev=0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def cross_attention(p, x, enc_kv, cfg: ModelConfig):
    """x (B,S,D); enc_kv: {"k","v": (B,T,H,D)} precomputed from encoder."""
    b, s, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = dense(p["wq"], x).reshape(b, s, h, hd)
    k, v = enc_kv["k"].astype(x.dtype), enc_kv["v"].astype(x.dtype)
    sc = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / math.sqrt(hd)
    probs = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhst,bthd->bshd", probs, v).reshape(b, s, h * hd)
    return dense(p["wo"], out)


def encode_cross_kv(p, enc_out, cfg: ModelConfig):
    b, t, _ = enc_out.shape
    h, hd = cfg.num_heads, cfg.head_dim
    return {
        "k": dense(p["wk"], enc_out).reshape(b, t, h, hd),
        "v": dense(p["wv"], enc_out).reshape(b, t, h, hd),
    }
