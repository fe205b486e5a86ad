"""Seeded random weights, made by the benchmark and not by the program.

The benchmark draws every weight itself, in its own plain layout (one
dict of arrays per model, layers stacked on a leading axis), in one
jitted call on the device, in the dtype the configuration serves them in.
The plain reference reads that layout directly. The backbone's layout is
its architecture's (``bench/archs/<architecture>.py``: ``shapes``); the
LSTM draft's is here. ``to_program`` and ``to_program_lstm`` only re-nest
the same arrays into the parameter tree the program's ``init`` would
build, and ``check_tree`` checks the result against the program's own
tree structure, shapes and dtypes, so a change of layout in the program
fails loudly instead of serving unrelated weights.

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


def check_tree(tree, reference, what: str):
    got = jax.tree.map(lambda a: (a.shape, a.dtype), tree)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), reference)
    if jax.tree.structure(got) != jax.tree.structure(want) or got != want:
        raise ValueError(f"{what}: the program's parameter tree no longer "
                         f"matches the benchmark's weights")
    return tree


def to_program_lstm(w: dict, model, key) -> dict:
    n = model.cfg.num_layers
    tree = {
        "embed": {"table": w["embed"]},
        "layers": [{"wx": {"w": w[f"wx{i}"]}, "wh": {"w": w[f"wh{i}"]}}
                   for i in range(n)],
        "head": {"w": w["head"]},
    }
    return check_tree(tree, jax.eval_shape(model.init, key), "draft")


def cast(w: dict, dtype) -> dict:
    return {k: jnp.asarray(v, dtype) for k, v in w.items()}
