"""DraftDecoder: fixed-reduction-order forward for the AR draft engine.

``DraftDecoder(model).forward_chunk(params, toks (B, S), cache, pos)``
replaces ``Model.decode_step`` (S=1) AND ``Model.prefill`` (S=P) with one
shared code path built from the token-row-block Pallas kernels in
kernel.py. Because both call sites run the SAME kernels at the SAME block
shapes — only the token-grid size differs — a multi-token batched prefill is
bit-identical to scanning the tokens one at a time, which is what lets
``drafting/ar_engine.py`` flip ``prefill_mode="batched"`` to default
without giving up its oracle bit-exactness contract.

Supported config subset (``draft_decode_supported``): plain decoder-only
attention stacks in float32 — ``pattern=("attn",)``-style uniform attn
layers, layernorm/rmsnorm, (gated) MLP, standard/none RoPE, optional
bias, tied or untied head. Anything exotic (qk-norm, post-norms, logit
softcap, M-RoPE/dual-RoPE, MoE/SSM kinds, encoder-decoder, bf16) falls
back to the XLA path in the adapter.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.draft_decode.kernel import (
    ROWS, attn_cached_pallas, head_pallas, pad_rows, post_attn_pallas,
    qkv_rope_pallas,
)


def draft_decode_supported(cfg) -> bool:
    """True when ``cfg`` is in the kernel path's supported subset."""
    try:
        attn_only = (tuple(cfg.prefix) == ()
                     and set(cfg.pattern) == {"attn"})
    except Exception:
        return False
    return bool(
        attn_only
        and not cfg.is_encoder_decoder
        and cfg.family != "vlm"
        and cfg.dtype == "float32"
        and cfg.param_dtype == "float32"
        and cfg.norm in ("layernorm", "rmsnorm")
        and cfg.act in ("gelu", "silu", "relu")
        and cfg.rope_type in ("default", "none")
        and not cfg.qk_norm
        and not cfg.post_norms
        and cfg.attn_logit_softcap == 0.0
        and not cfg.embed_scale
    )


@dataclasses.dataclass(frozen=True)
class DraftDecoder:
    """Kernelized draft forward over a ``models.Model``'s params/cache.

    Operates directly on the existing ``init_stack_cache`` pytree (stacked
    ``blocks/p0`` k/v leaves + per-block ``pos`` cursor) so the engine's
    pooling/rewind machinery needs no changes. ``interpret=None`` resolves
    through the central ``kernels.resolve_interpret``.
    """

    model: Any
    interpret: Optional[bool] = None

    def __post_init__(self):
        cfg = self.model.cfg
        if not draft_decode_supported(cfg):
            raise ValueError(
                f"config {cfg.name!r} is outside the draft_decode kernel "
                "subset (see draft_decode_supported)")

    # -- one transformer layer over the flattened token rows ---------------

    def _layer(self, lp, x2, kbuf, vbuf, start, pos0, pos_r, b, s,
               interpret):
        """x2 / pos_r carry the B*S token rows padded to a multiple of
        ROWS; kbuf / vbuf are (B, T, KH*hd)."""
        cfg = self.model.cfg
        kh, hd = cfg.num_kv_heads, cfg.head_dim
        r, rp = b * s, x2.shape[0]
        q, k, v = qkv_rope_pallas(
            x2, pos_r, lp["ln1"], lp["attn"],
            norm=cfg.norm, eps=cfg.norm_eps, use_bias=cfg.use_bias,
            use_rope=cfg.rope_type == "default", theta=cfg.rope_theta,
            heads=cfg.num_heads, kv_heads=kh, head_dim=hd,
            interpret=interpret)
        kbuf = jax.lax.dynamic_update_slice(
            kbuf, k[:r].reshape(b, s, kh * hd), (0, start, 0))
        vbuf = jax.lax.dynamic_update_slice(
            vbuf, v[:r].reshape(b, s, kh * hd), (0, start, 0))
        a = attn_cached_pallas(
            q[:r].reshape(b, s, cfg.num_heads * hd), kbuf, vbuf, pos0,
            start + s, heads=cfg.num_heads, kv_heads=kh, head_dim=hd,
            interpret=interpret)
        x2 = post_attn_pallas(
            pad_rows(a.reshape(r, cfg.num_heads * hd), rp), x2, lp["attn"],
            lp["ln2"], lp["mlp"], norm=cfg.norm, eps=cfg.norm_eps,
            use_bias=cfg.use_bias, act=cfg.act, interpret=interpret)
        return x2, kbuf, vbuf

    # -- the shared decode/prefill forward ---------------------------------

    def forward_chunk(self, params, toks, cache, pos):
        """toks (B, S) int32 -> (logits (B, S, V) f32, new cache).

        ``pos`` is the rope/mask offset of the chunk's first token; KV
        writes go at each layer's own cache cursor (kept in sync with
        ``pos`` by the engine, exactly like the XLA path).
        """
        cfg = self.model.cfg
        interpret = resolve_interpret(self.interpret)
        b, s = toks.shape
        d = cfg.d_model
        kh, hd = cfg.num_kv_heads, cfg.head_dim
        reps, rem = cfg.scan_split()

        rp = -(-(b * s) // ROWS) * ROWS
        table = params["embed"]["table"].astype(jnp.float32)
        x2 = pad_rows(jnp.take(table, toks, axis=0).reshape(b * s, d), rp)
        pos0 = jnp.asarray(pos, jnp.int32)
        pos_r = pad_rows(jnp.broadcast_to(
            pos0 + jnp.arange(s, dtype=jnp.int32)[None, :], (b, s)
        ).reshape(b * s, 1), rp)

        new_cache: dict = {"blocks": {}, "rem": {}, "pre": {}}

        if reps:
            bp = params["stack"]["blocks"]["p0"]
            bc = cache["blocks"]["p0"]
            # stacked (reps, B, T, KH, hd) leaves: flatten heads for the
            # kernels, slice/restack per layer (pure data movement)
            kbufs, vbufs = bc["k"], bc["v"]
            t = kbufs.shape[2]
            for i in range(reps):
                lp = jax.tree.map(lambda a, i=i: a[i], bp)
                start = bc["pos"][i].astype(jnp.int32)
                kb = kbufs[i].reshape(b, t, kh * hd)
                vb = vbufs[i].reshape(b, t, kh * hd)
                x2, kb, vb = self._layer(lp, x2, kb, vb, start, pos0, pos_r,
                                         b, s, interpret)
                kbufs = kbufs.at[i].set(kb.reshape(b, t, kh, hd))
                vbufs = vbufs.at[i].set(vb.reshape(b, t, kh, hd))
            new_cache["blocks"]["p0"] = {
                "k": kbufs, "v": vbufs,
                "pos": bc["pos"] + jnp.asarray(s, bc["pos"].dtype),
            }

        for j in range(len(rem)):
            lp = params["stack"]["rem"][f"r{j}"]
            rc = cache["rem"][f"r{j}"]
            t = rc["k"].shape[1]
            start = rc["pos"].astype(jnp.int32)
            kb = rc["k"].reshape(b, t, kh * hd)
            vb = rc["v"].reshape(b, t, kh * hd)
            x2, kb, vb = self._layer(lp, x2, kb, vb, start, pos0, pos_r,
                                     b, s, interpret)
            new_cache["rem"][f"r{j}"] = {
                "k": kb.reshape(b, t, kh, hd), "v": vb.reshape(b, t, kh, hd),
                "pos": rc["pos"] + jnp.asarray(s, rc["pos"].dtype),
            }

        if cfg.tie_embeddings:
            w = params["embed"]["table"].astype(jnp.float32).T
        else:
            w = params["head"]["w"].astype(jnp.float32)
        logits = head_pallas(x2, params["final_norm"], w, norm=cfg.norm,
                             eps=cfg.norm_eps, interpret=interpret)
        return logits[:b * s].reshape(b, s, cfg.vocab_size), new_cache
