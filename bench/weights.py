"""Seeded random weights, made by the benchmark and not by the program.

The benchmark draws every weight itself, in its own plain layout (one
dict of arrays per model, layers stacked on a leading axis), in one
jitted call on the device, in the dtype the configuration serves them in.
The plain reference reads that layout directly. ``to_program_*`` only
re-nests the same arrays into the parameter tree the program's ``init``
would build, and checks the result against the program's own tree
structure, shapes and dtypes, so a change of layout in the program fails
loudly here instead of serving unrelated weights.

Scales: dense weights N(0, 1/fan_in), so every layer contributes at
order one and attention is not uniform; embedding tables N(0, 0.02^2);
norm scales 1 + N(0, 0.1^2); biases N(0, 0.02^2). Random biases and
scales make a dropped bias or norm term show in the comparison.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def root_key(seed: int):
    """A PRNG key from all bits of ``seed`` (``jax.random.key`` keeps only
    the low 32 when 64-bit mode is off)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def dit_shapes(m: dict) -> dict:
    """Leaf name -> shape of the DiT-style denoiser described by the
    configuration dict ``m`` (see ``bench/configs``)."""
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


def lstm_shapes(dr: dict, vocab: int) -> dict:
    e, h = dr["embed_dim"], dr["hidden"]
    s = {"embed": (vocab, e), "head": (h, vocab)}
    for i in range(dr["num_layers"]):
        s[f"wx{i}"] = ((e if i == 0 else h), 4 * h)
        s[f"wh{i}"] = (h, 4 * h)
    return s


def _draw(key, name: str, shape, dtype):
    if name.endswith("_scale"):
        w = 1.0 + 0.1 * jax.random.normal(key, shape)
    elif name == "embed" or name.startswith("b") or name.endswith("_bias"):
        w = 0.02 * jax.random.normal(key, shape)
    else:
        w = jax.random.normal(key, shape) / math.sqrt(shape[-2])
    return w.astype(dtype)


@partial(jax.jit, static_argnums=(1, 2))
def _make(key, shapes: tuple, dtype: str):
    return {name: _draw(jax.random.fold_in(key, i), name, shape, dtype)
            for i, (name, shape) in enumerate(shapes)}


def make(seed: int, shapes: dict, dtype: str, stream: int) -> dict:
    """All leaves of ``shapes`` in one jitted call from ``seed``;
    ``stream`` separates the weights of different models of one run."""
    key = jax.random.fold_in(root_key(seed), stream)
    return _make(key, tuple(sorted(shapes.items())), dtype)


def _check(tree, reference, what: str):
    got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), reference)
    if jax.tree.structure(got) != jax.tree.structure(want) or got != want:
        raise ValueError(f"{what}: the program's parameter tree no longer "
                         f"matches the benchmark's weights")
    return tree


def to_program_dit(w: dict, model, key) -> dict:
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
    return _check(tree, jax.eval_shape(model.init, key), "backbone")


def to_program_lstm(w: dict, model, key) -> dict:
    n = model.cfg.num_layers
    tree = {
        "embed": {"table": w["embed"]},
        "layers": [{"wx": {"w": w[f"wx{i}"]}, "wh": {"w": w[f"wh{i}"]}}
                   for i in range(n)],
        "head": {"w": w["head"]},
    }
    return _check(tree, jax.eval_shape(model.init, key), "draft")


def cast(w: dict, dtype) -> dict:
    return {k: jnp.asarray(v, dtype) for k, v in w.items()}
