"""Meshes. IMPORTANT: functions, not module-level constants — importing
this module never touches jax device state.

``make_production_mesh`` is the v5e pod layout (16x16 = 256 chips per pod,
two pods for multi-pod) that ``dryrun.py`` compiles for on forced host
devices. ``make_local_mesh`` spans the devices attached to this process.
Both mesh axes are ``Auto``: the shard rules place arrays with sharding
constraints that the compiler propagates (``jax.make_mesh`` would make
them ``Explicit``, which the model's matmuls do not annotate).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model_parallel: int = 1):
    """(data, model) mesh over every attached device: the chips of one
    host (2x2 on a four-chip v5e host), or the virtual CPU devices of
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), axis_types=(AxisType.Auto,) * 2)


# v5e hardware constants for the roofline terms (per chip)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # bytes/s
ICI_BW = 50e9                   # bytes/s per link
