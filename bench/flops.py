"""Operations and bytes of the served programs, from the configuration and
the dispatched shapes, and the table of chip peaks.

``param_count``, ``forward_flops`` and ``forward_bytes`` count one
denoiser evaluation (``dfm_apply``) of ``rows`` rows of ``seq`` tokens:
matmul multiply-adds as 2 operations each, and the least HBM traffic.
The architecture's module (``bench/archs/<architecture>.py``) counts
them.
"""

from __future__ import annotations

import json
from pathlib import Path

from bench import archs

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


def param_count(m: dict) -> int:
    return archs.load(m["architecture"]).param_count(m)


def forward_flops(m: dict, rows: int, seq: int) -> float:
    return archs.load(m["architecture"]).forward_flops(m, rows, seq)


def forward_bytes(m: dict, rows: int, seq: int) -> float:
    return archs.load(m["architecture"]).forward_bytes(m, rows, seq)


def roofline_s(m: dict, rows: int, seq: int, peak: dict) -> float:
    """Least time of one evaluation on the chip: the larger of operations
    over the bf16 peak and bytes over HBM bandwidth. The bf16 peak bounds
    float32 configurations too, since the chip's default float32 matmul
    is one bf16 pass."""
    return max(forward_flops(m, rows, seq) / peak["bf16_flops_per_s"],
               forward_bytes(m, rows, seq) / peak["hbm_bytes_per_s"])
