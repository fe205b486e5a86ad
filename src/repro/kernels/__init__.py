"""Pallas TPU kernels for the performance-critical hot spots:
  ws_step      — streamed vocab-tiled warm-start Euler sampling step with
                 in-kernel PRNG (the paper's inner loop)
  ws_fused     — multi-step fused refine megakernel: K consecutive Euler
                 warm-start sampling steps in ONE dispatch, token state and
                 accumulators carried in VMEM scratch across steps
  flash_attn   — flash attention: the refine's bidirectional attention
                 with the whole key range in one VMEM block, KV heads by
                 the index map; online softmax with sliding-window block
                 skipping past that
  draft_decode — fixed-reduction-order decode-step kernels for the AR
                 draft engine (bit-identical batched prefill)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py
(backend-aware jit'd dispatcher) and ref.py (pure-jnp oracle); tests
sweep shapes/dtypes in interpret mode.

``resolve_interpret`` below is THE backend/interpret resolver every
kernel package dispatches through (it used to be duplicated per
package): ``None`` resolves at trace time to "interpret iff the backend
is not TPU", so kernels compile on real TPUs and run the Pallas
interpreter everywhere else.
"""
from typing import Optional

import jax


def default_platform() -> str:
    """The platform a computation traced now runs on: the default
    device's where one is set (``jax.default_device``, as a host-side
    reference on a TPU machine does), else the default backend's."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Resolve an ``interpret=None`` kernel argument at trace time.

    ``None`` -> interpret unless running on a real TPU backend; a bool is
    honoured verbatim. Shared by ws_step, ws_fused, flash_attn and
    draft_decode so backend detection can't drift between packages.
    """
    if interpret is None:
        return default_platform() != "tpu"
    return bool(interpret)


def is_tpu_backend() -> bool:
    """True when the default JAX backend is a real TPU (trace-time check
    used to auto-select hardware PRNG / compiled kernel paths)."""
    return default_platform() == "tpu"


from repro.kernels.ws_step import (
    make_ws_step_fn, pick_tiles, ws_step, ws_step_ref, ws_step_ref_streamed,
    ws_step_streamed_pallas,
)
from repro.kernels.ws_fused import (
    make_ws_fused_fn, pick_tiles_fused, ws_fused_steps,
)
from repro.kernels.flash_attn import flash_attention, flash_attention_ref
from repro.kernels.draft_decode import (
    DraftDecoder, draft_decode_supported,
)

__all__ = ["default_platform", "resolve_interpret", "is_tpu_backend",
           "ws_step", "make_ws_step_fn", "pick_tiles", "ws_step_ref",
           "ws_step_ref_streamed", "ws_step_streamed_pallas",
           "ws_fused_steps", "make_ws_fused_fn", "pick_tiles_fused",
           "flash_attention", "flash_attention_ref",
           "DraftDecoder", "draft_decode_supported"]
