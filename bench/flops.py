"""Operations and bytes of the served programs, from the configuration and
the dispatched shapes, and the table of chip peaks.

``forward_flops`` counts the multiply-adds of one denoiser evaluation
(``dfm_apply``) as 2 operations each: the q/k/v/o projections, the
attention scores and the attention-weighted values (``4 * S^2 * d`` per
layer at full head width), the MLP, the time embedding and the output
head. Norms, activations, softmax and the Euler step are left out; they
are a few operations per element against thousands per matmul row.

``forward_bytes`` is the least HBM traffic of one evaluation: every
weight read once, the residual stream read and written once per layer,
and the logits written once and read once by the sampling step.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device missing from the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add its published numbers")
    return table[device_kind]


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
    act = DTYPE_BYTES[m["activation_dtype"]]
    weights = param_count(m) * DTYPE_BYTES[m["weight_dtype"]]
    residual = n * 2 * rows * seq * d * act
    logits = 2 * rows * seq * v * act
    return float(weights + residual + logits)


def roofline_s(m: dict, rows: int, seq: int, peak: dict) -> float:
    """Least time of one evaluation on the chip: the larger of operations
    over the bf16 peak and bytes over HBM bandwidth. The bf16 peak bounds
    float32 configurations too, since the chip's default float32 matmul
    is one bf16 pass."""
    return max(forward_flops(m, rows, seq) / peak["bf16_flops_per_s"],
               forward_bytes(m, rows, seq) / peak["hbm_bytes_per_s"])
