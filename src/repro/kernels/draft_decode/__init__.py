"""Fixed-reduction-order Pallas decode kernels for the AR draft engine.

``DraftDecoder.forward_chunk`` is one shared token-row-block kernel path for
decode (S=1) and batched prefill (S=P), making the two bit-identical —
see kernel.py for the discipline and ops.py for the config gate.
"""
from repro.kernels.draft_decode.ops import DraftDecoder, draft_decode_supported

__all__ = ["DraftDecoder", "draft_decode_supported"]
