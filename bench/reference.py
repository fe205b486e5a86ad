"""Plain ``jax.numpy`` reference of the served pipeline, independent of
the program: it imports nothing from ``src/`` and reads only the
benchmark's own weights (``bench/weights.py`` layout).

What it computes, for rows of the timed path:

* the draft LSTM's next-token logits, teacher-forced on a row's draft;
* the denoiser's logits, by the ``logits`` of the configuration's
  architecture module (``bench/archs/<architecture>.py``), which builds
  them from the generic helpers here (``mm``, ``_einsum``, ``_act``,
  ``layer_norm``, ``gelu_tanh``);
* the warm-start Euler chain over ``t in [t0, 1]`` with the per-row
  Gumbel noise the served path draws, from a row's draft: one backbone
  evaluation, one Euler update and one Gumbel-max draw per step.

Modes: ``"f32"`` is float32 with every matmul at "highest" precision;
``"f32-default"`` is float32 with matmuls at JAX's default precision,
which on a TPU is one bf16 pass with float32 accumulation: float32 as a
configuration that states float32 is computed on the chip. ``"bf16"``
computes in bfloat16 with float32 norm statistics and softmax, the way a
bf16 model is served. ``"int8"`` is ``"bf16"`` with every weight matmul
taking int8 inputs (per-row activation and per-column weight scales,
int32 accumulation). Which mode is the reference of a configuration, and
which is its control, is in ``bench/limits/<config>.json``.
"""

from __future__ import annotations

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import archs

HIGHEST = jax.lax.Precision.HIGHEST
# the served path's key streams: fold_in(fold_in(key(seed), sample), s)
DRAFT_STREAM, FLOW_STREAM = 0, 1


# ---------------------------------------------------------------------------
# keys and schedule
# ---------------------------------------------------------------------------

@jax.jit
def row_keys(seeds, sample_idx):
    """(draft_keys, flow_keys) of rows given by request seed and sample."""
    def one(s, i):
        base = jax.random.fold_in(jax.random.key(s), i)
        return (jax.random.fold_in(base, DRAFT_STREAM),
                jax.random.fold_in(base, FLOW_STREAM))
    return jax.vmap(one)(jnp.asarray(seeds, jnp.int32),
                         jnp.asarray(sample_idx, jnp.int32))


def warm_steps(cold_nfe: int, t0: float) -> int:
    """Euler steps of size 1/cold_nfe from t0 to 1."""
    return max(1, math.ceil(cold_nfe * (1.0 - t0) - 1e-9))


def schedule(cold_nfe: int, t0: float):
    """Per-step times and step sizes; the last step is cut to land on 1."""
    n = warm_steps(cold_nfe, t0)
    h = 1.0 / cold_nfe
    ts = (t0 + np.arange(n, dtype=np.float64) * h).astype(np.float32)
    hs = np.minimum(np.float32(h), np.float32(1.0) - ts).astype(np.float32)
    return ts, hs


# ---------------------------------------------------------------------------
# arithmetic by mode
# ---------------------------------------------------------------------------

def _act(mode: str):
    return jnp.float32 if mode.startswith("f32") else jnp.bfloat16


def _quant(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-30)
    return jnp.round(x / scale).astype(jnp.int8), scale


def mm(x, w, mode: str):
    """x (..., k) @ w (k, n) in the mode's arithmetic."""
    if mode.startswith("f32"):
        return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                          precision=_precision(mode))
    if mode == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16))
    if mode == "int8":
        xq, xs = _quant(x.astype(jnp.float32), -1)
        wq, ws = _quant(w.astype(jnp.float32), 0)
        acc = jnp.matmul(xq, wq, preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * xs * ws).astype(jnp.bfloat16)
    raise ValueError(f"unknown mode {mode!r}")


def _precision(mode: str):
    return HIGHEST if mode == "f32" else jax.lax.Precision.DEFAULT


def _einsum(spec, a, b, mode):
    if mode.startswith("f32"):
        return jnp.einsum(spec, a, b, precision=_precision(mode))
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) / jnp.sqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def gelu_tanh(x):
    xf = x.astype(jnp.float32)
    c = math.sqrt(2.0 / math.pi)
    return (0.5 * xf * (1.0 + jnp.tanh(c * (xf + 0.044715 * xf ** 3)))
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# the denoiser's chain
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("m_json", "mode"))
def _chain(w, x, flow_keys, ts, hs, *, m_json, mode):
    m = json.loads(m_json)
    logits_fn = archs.load(m["architecture"]).logits
    b, n = x.shape
    v = m["vocab_size"]

    def step(carry, inp):
        x, margin = carry
        t, h, i = inp
        logits = logits_fn(w, m, x, jnp.full((b,), t), mode)
        p1 = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        a = jnp.clip(h / jnp.maximum(1.0 - t, 1e-4), 0.0, 1.0)
        p = (1.0 - a) * jax.nn.one_hot(x, v, dtype=jnp.float32) + a * p1
        keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(flow_keys, i)
        g = jax.vmap(lambda k: jax.random.gumbel(k, (n, v), jnp.float32))(keys)
        score = jnp.log(jnp.maximum(p, 1e-30)) + g
        top2, idx = jax.lax.top_k(score, 2)
        margin = jnp.minimum(margin, top2[..., 0] - top2[..., 1])
        return (idx[..., 0].astype(jnp.int32), margin), None

    steps = jnp.arange(ts.shape[0], dtype=jnp.int32)
    margin0 = jnp.full((b, n), jnp.inf, jnp.float32)
    (x, margin), _ = jax.lax.scan(step, (x, margin0), (ts, hs, steps))
    return x, margin


def refine_chain(w, m: dict, x, flow_keys, cold_nfe: int, t0: float,
                 mode: str):
    """Final tokens (B, N) of the warm-start chain from drafts x (B, N),
    and per position the smallest margin (best Gumbel score minus the
    second best) of any of its steps: how clearly the chain decided it."""
    ts, hs = schedule(cold_nfe, t0)
    return _chain(w, jnp.asarray(x, jnp.int32), flow_keys, jnp.asarray(ts),
                  jnp.asarray(hs), m_json=_frozen(m), mode=mode)


# ---------------------------------------------------------------------------
# the draft LSTM
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("layers", "bos", "mode"))
def _lstm_scores(wl, tokens, draft_keys, *, layers, bos, mode):
    """Per position: Gumbel scores (B, N, V) of the next token given the
    row's draft prefix, with the served path's per-position noise."""
    b, n = tokens.shape
    hidden = wl["wh0"].shape[0]
    inputs = jnp.concatenate(
        [jnp.full((b, 1), bos, jnp.int32), tokens[:, :-1]], axis=1)
    act = _act(mode)

    def step(state, inp):
        tok, i = inp
        x = jnp.take(wl["embed"], tok, axis=0).astype(act)
        new = []
        for l in range(layers):
            h, c = state[l]
            g = (mm(x, wl[f"wx{l}"], mode).astype(act)
                 + mm(h, wl[f"wh{l}"], mode).astype(act))
            gi, gf, gz, go = jnp.split(g, 4, axis=-1)
            c = jax.nn.sigmoid(gf + 1.0) * c + jax.nn.sigmoid(gi) * jnp.tanh(gz)
            h = jax.nn.sigmoid(go) * jnp.tanh(c)
            new.append((h, c))
            x = h
        logits = mm(x, wl["head"], mode).astype(act)
        keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(draft_keys, i)
        g = jax.vmap(lambda k: jax.random.gumbel(
            k, (logits.shape[-1],), jnp.float32))(keys)
        return new, (logits.astype(jnp.float32) + g, logits.astype(act)
                     + g.astype(act))

    z = jnp.zeros((b, hidden), act)
    state0 = [(z, z) for _ in range(layers)]
    _, (score, score_mode) = jax.lax.scan(
        step, state0, (inputs.T, jnp.arange(n, dtype=jnp.int32)))
    return jnp.moveaxis(score, 0, 1), jnp.moveaxis(score_mode, 0, 1)


def draft_gaps(wl, layers: int, bos: int, tokens, draft_keys, mode: str,
               control: str = ""):
    """Gap (B, N) by which each draft token's Gumbel score in the
    reference ``mode`` lies below the best score there. With ``control``
    (a mode), the gap of the token that the control, teacher-forced on the
    same draft, puts first."""
    tokens = jnp.asarray(tokens, jnp.int32)
    score, _ = _lstm_scores(wl, tokens, draft_keys, layers=layers, bos=bos,
                            mode=mode)
    if control:
        _, cscore = _lstm_scores(wl, tokens, draft_keys, layers=layers,
                                 bos=bos, mode=control)
        tokens = jnp.argmax(cscore, -1).astype(jnp.int32)
    best = jnp.max(score, -1)
    got = jnp.take_along_axis(score, tokens[..., None], -1)[..., 0]
    return np.asarray(best - got)


def _frozen(m: dict) -> str:
    """The whole ``model`` dict as the chain's static argument: every key
    of every architecture, nested groups included, reaches the jit."""
    return json.dumps(m, sort_keys=True)
