"""The DiT-style denoiser served through ``Model.dfm_apply``: bidirectional
attention with RoPE (GQA where ``num_key_value_heads`` is smaller),
additive Fourier time embedding, pre-LayerNorm blocks, non-gated tanh
GELU MLP, final LayerNorm, untied or tied head.

``forward_flops`` counts the multiply-adds of one denoiser evaluation as
2 operations each: the q/k/v/o projections, the attention scores and the
attention-weighted values (``4 * S^2 * d`` per layer at full head width),
the MLP, the time embedding and the output head. Norms, activations,
softmax and the Euler step are left out; they are a few operations per
element against thousands per matmul row.

``forward_bytes`` is the least HBM traffic of one evaluation: every
weight read once, the residual stream read and written once per layer,
and the logits written once and read once by the sampling step.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import flops, weights
from bench.reference import _act, _einsum, gelu_tanh, layer_norm, mm


def model_config(name: str, m: dict):
    from repro.configs.base import ModelConfig

    want = {"norm_type": "layer_norm", "hidden_act": "gelu_pytorch_tanh"}
    for k, v in want.items():
        if m[k] != v:
            raise ValueError(f"{name}: {k}={m[k]!r}; the harness and the "
                             f"reference implement {v!r}")
    return ModelConfig(
        name=name, family="dense", num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        pattern=("attn",), rope_theta=m["rope_theta"],
        use_bias=m["use_bias"], norm="layernorm", norm_eps=m["norm_epsilon"],
        act="gelu", mlp_gated=False, tie_embeddings=m["tie_word_embeddings"],
        max_seq_len=m["max_position_embeddings"],
        dtype=m["activation_dtype"], param_dtype=m["weight_dtype"],
        time_embed_dim=m["time_embed_dim"])


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def shapes(m: dict) -> dict:
    """Leaf name -> shape of the denoiser described by the configuration
    dict ``m`` (see ``bench/configs``)."""
    d, ff, v, n = (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
                   m["num_hidden_layers"])
    hd = m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    te = m["time_embed_dim"]
    s = {
        "embed": (v, d),
        "time_w1": (te, 4 * te), "time_w2": (4 * te, d),
        "ln1_scale": (n, d), "ln1_bias": (n, d),
        "wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv), "wo": (n, q, d),
        "ln2_scale": (n, d), "ln2_bias": (n, d),
        "w_up": (n, d, ff), "w_down": (n, ff, d),
        "final_scale": (d,), "final_bias": (d,),
    }
    if m["use_bias"]:
        s.update(bq=(n, q), bk=(n, kv), bv=(n, kv), bo=(n, d),
                 b_up=(n, ff), b_down=(n, d))
    if not m["tie_word_embeddings"]:
        s["head"] = (d, v)
    return s


def to_program(w: dict, model, key) -> dict:
    """The program's ``Model`` parameter tree holding the arrays of ``w``."""
    def dense(name, bias=None):
        p = {"w": w[name]}
        if bias is not None and bias in w:
            p["b"] = w[bias]
        return p

    block = {
        "ln1": {"scale": w["ln1_scale"], "bias": w["ln1_bias"]},
        "attn": {"wq": dense("wq", "bq"), "wk": dense("wk", "bk"),
                 "wv": dense("wv", "bv"), "wo": dense("wo", "bo")},
        "ln2": {"scale": w["ln2_scale"], "bias": w["ln2_bias"]},
        "mlp": {"up": dense("w_up", "b_up"), "down": dense("w_down", "b_down")},
    }
    tree = {
        "embed": {"table": w["embed"]},
        "stack": {"blocks": {"p0": block}, "rem": {}, "pre": {}},
        "final_norm": {"scale": w["final_scale"], "bias": w["final_bias"]},
        "time": {"w1": {"w": w["time_w1"]}, "w2": {"w": w["time_w2"]}},
    }
    if "head" in w:
        tree["head"] = {"w": w["head"]}
    return weights.check_tree(tree, jax.eval_shape(model.init, key),
                              "backbone")


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

def logits(w: dict, m: dict, tokens, t, mode: str):
    """Logits (B, N, V) of the denoiser at tokens (B, N), times t (B,)."""
    act = _act(mode)
    b, n = tokens.shape
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    grp = nh // nkv
    eps = m["norm_epsilon"]

    def lin(x, name, l=None):
        wt = w[name] if l is None else w[name][l]
        y = mm(x, wt, mode).astype(act)
        bname = {"wq": "bq", "wk": "bk", "wv": "bv", "wo": "bo",
                 "w_up": "b_up", "w_down": "b_down"}.get(name)
        if bname in w:
            y = y + (w[bname] if l is None else w[bname][l]).astype(act)
        return y

    x = jnp.take(w["embed"], tokens, axis=0).astype(act)
    half = m["time_embed_dim"] // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = t.astype(jnp.float32)[:, None] * freqs[None] * 1000.0
    feats = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(act)
    temb = mm(jax.nn.silu(mm(feats, w["time_w1"], mode).astype(act)),
              w["time_w2"], mode).astype(act)
    x = x + temb[:, None, :]

    rh = hd // 2
    inv = m["rope_theta"] ** (-jnp.arange(rh, dtype=jnp.float32) / rh)
    pos_ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None]
    sin, cos = jnp.sin(pos_ang).astype(act), jnp.cos(pos_ang).astype(act)

    def rope(v):                                     # (B, N, H, hd)
        v1, v2 = v[..., :rh], v[..., rh:]
        s, c = sin[None, :, None], cos[None, :, None]
        return jnp.concatenate([v1 * c - v2 * s, v2 * c + v1 * s], -1)

    def layer(x, l):
        h = layer_norm(x, w["ln1_scale"][l], w["ln1_bias"][l], eps)
        q = rope(lin(h, "wq", l).reshape(b, n, nh, hd))
        k = rope(lin(h, "wk", l).reshape(b, n, nkv, hd))
        v = lin(h, "wv", l).reshape(b, n, nkv, hd)
        # query head j reads key/value head j // grp
        k = jnp.repeat(k, grp, axis=2)
        v = jnp.repeat(v, grp, axis=2)
        s = _einsum("bqhd,bkhd->bhqk", q, k, mode).astype(jnp.float32)
        p = jax.nn.softmax(s / math.sqrt(hd), axis=-1).astype(act)
        o = _einsum("bhqk,bkhd->bqhd", p, v, mode).astype(act)
        x = x + lin(o.reshape(b, n, nh * hd), "wo", l)
        h = layer_norm(x, w["ln2_scale"][l], w["ln2_bias"][l], eps)
        x = x + lin(gelu_tanh(lin(h, "w_up", l)), "w_down", l)
        return x, None

    x, _ = jax.lax.scan(layer, x, jnp.arange(m["num_hidden_layers"]))
    x = layer_norm(x, w["final_scale"], w["final_bias"], eps)
    head = w["head"] if "head" in w else w["embed"].T
    return mm(x, head, mode).astype(act)


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------

def _widths(m: dict):
    hd = m["head_dim"]
    return (m["hidden_size"], m["intermediate_size"], m["vocab_size"],
            m["num_hidden_layers"], m["num_attention_heads"] * hd,
            m["num_key_value_heads"] * hd, m["time_embed_dim"])


def param_count(m: dict) -> int:
    d, ff, v, n, q, kv, te = _widths(m)
    per_layer = d * (q + 2 * kv) + q * d + 2 * d * ff + 4 * d
    if m["use_bias"]:
        per_layer += q + 2 * kv + 2 * d + ff
    head = 0 if m["tie_word_embeddings"] else d * v
    return n * per_layer + v * d + head + te * 4 * te + 4 * te * d + 2 * d


def forward_flops(m: dict, rows: int, seq: int) -> float:
    d, ff, v, n, q, kv, te = _widths(m)
    per_token = n * (2 * d * (q + 2 * kv) + 2 * q * d + 4 * d * ff
                     + 4 * seq * q) + 2 * d * v
    time = 2 * (te * 4 * te + 4 * te * d)
    return float(rows * (seq * per_token + time))


def forward_bytes(m: dict, rows: int, seq: int) -> float:
    d, _, v, n, _, _, _ = _widths(m)
    act = flops.DTYPE_BYTES[m["activation_dtype"]]
    weight_bytes = param_count(m) * flops.DTYPE_BYTES[m["weight_dtype"]]
    residual = n * 2 * rows * seq * d * act
    logit_bytes = 2 * rows * seq * v * act
    return float(weight_bytes + residual + logit_bytes)
