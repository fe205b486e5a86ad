"""KV-cached autoregressive draft engine.

The paper's speed-up guarantee assumes the draft stage is *negligible*
next to one backbone NFE. That only holds if draft generation is an
actual serving component: cache-backed AR decode in ONE device dispatch,
not a fresh O(L^2) recompute per token. This module provides that engine
for the model-zoo draft substrates (the LSTM of §4.2 and tiny causal
transformers):

  * **preallocated, donated cache** — the KV buffer (attention adapters:
    stacked ``(layers, B, T, heads, head_dim)`` leaves; LSTM adapter:
    ``(layers, B, hidden)`` h/c state) is allocated once per row count at
    ``max_len`` capacity and *donated* through every jit dispatch, so
    steady-state decoding allocates nothing;
  * **prefill + decode phases** — the prompt is consumed by a prefill
    pass (scanned single-token by default, see below), then ``seq_len``
    tokens are sampled by one ``lax.scan`` decode dispatch;
  * **cross-micro-batch cache reuse** — the engine keeps the post-prefill
    cache per row-count; micro-batches sharing the same prompt prefix
    skip the prefill entirely (attention adapters just rewind the cache
    ``pos`` — KV rows past the prefix are masked by cache validity, so
    stale state from the previous micro-batch can never leak);
  * **row-keyed determinism** — token ``i`` of row ``b`` is sampled with
    ``fold_in(keys[b], i)``: a row's draft depends only on its own key
    (and the shared prompt), never on its neighbours, its batch position,
    or the bucket length it was served at (drafts are prefix-stable:
    a row's first ``m`` tokens agree between ``seq_len = m`` and ``> m``).

Bit-exactness contract (tested against ``ref.oracle_generate_rows``):
every adapter evaluation a request sees must reproduce the cache-free
full-recompute oracle **bitwise** across prefill lengths, batch sizes
and partial cache reuse. How that is achieved depends on the substrate:

  * ``prefill_mode="scan"`` consumes the prompt single-token-at-a-time,
    so every evaluation is the decode shape — bit-exact by construction
    on any substrate, at O(P) dispatches.
  * ``prefill_mode="batched"`` consumes the prompt in ONE multi-token
    call. For adapters whose ``exact_batched_prefill`` is True this is
    *also* bit-exact: the LSTM's "batched" prefill is itself a scan of
    decode steps, and the transformer adapter routes through the
    ``kernels.draft_decode`` Pallas path, which processes every token in
    its own fixed-shape grid program so the reduction order of each dot,
    norm and softmax is identical at S=1 and S=P. Only the legacy XLA
    transformer path (``decode_impl="xla"``, or configs outside
    ``draft_decode_supported``) is float-tolerance (~1e-6), because XLA
    tiles batched matmuls differently than decode-shaped ones.

``prefill_mode=None`` (default) picks "batched" when the adapter
advertises ``exact_batched_prefill`` and "scan" otherwise — fast AND
bit-exact in the common case, degrading to the scan path only where
exactness would be lost.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import DraftDecoder, draft_decode_supported


# ---------------------------------------------------------------------------
# zoo adapters
# ---------------------------------------------------------------------------
# Adapter contract (all methods jit-traceable):
#   init_cache(batch, max_len)                  -> cache pytree
#   decode_step(params, tok (B,), cache, pos)   -> (logits (B, V), cache)
#   prefill_batched(params, toks (B,S), cache)  -> (logits (B, V), cache)
#   positional: True  -> cache carries write positions; prefix reuse is a
#                        host-side ``pos`` rewind (zero copy);
#               False -> cache is a recurrent state; prefix reuse keeps a
#                        snapshot and donates a copy into each decode.


@dataclasses.dataclass(frozen=True)
class TransformerDraftAdapter:
    """Zoo ``Model`` (decoder-only causal transformer) as draft substrate.

    The cache is ``models.transformer.init_stack_cache``'s pytree: the
    scanned layer stack holds its k/v leaves stacked ``(layers, B, T,
    kv_heads, head_dim)`` with a per-block write cursor ``pos``; cache
    validity masking (``k_valid``) guarantees positions >= the cursor are
    invisible, which is what makes cross-micro-batch buffer reuse safe.
    """

    model: Any                       # repro.models.Model
    cache_dtype: Any = jnp.float32   # draft models are small; keep f32
    decode_impl: str = "auto"        # "auto" | "kernel" | "xla"

    positional = True

    @functools.cached_property
    def _decoder(self):
        """The fixed-reduction-order Pallas path, or None for XLA.

        "auto" takes the kernel path whenever the config is inside the
        ``draft_decode_supported`` subset (and the cache is f32);
        "kernel" demands it; "xla" keeps the legacy float-tolerance path.
        """
        if self.decode_impl == "xla":
            return None
        supported = (draft_decode_supported(self.model.cfg)
                     and self.cache_dtype == jnp.float32)
        if self.decode_impl == "kernel":
            return DraftDecoder(model=self.model)   # raises if unsupported
        if self.decode_impl != "auto":
            raise ValueError(
                f"decode_impl must be auto|kernel|xla, got {self.decode_impl}")
        return DraftDecoder(model=self.model) if supported else None

    @property
    def exact_batched_prefill(self) -> bool:
        """True when ``prefill_batched`` is bit-identical to scanning."""
        return self._decoder is not None

    def init_cache(self, batch: int, max_len: int):
        return self.model.init_cache(batch, max_len, self.cache_dtype)

    def decode_step(self, params, tok, cache, pos):
        if self._decoder is not None:
            logits, cache = self._decoder.forward_chunk(
                params, tok[:, None], cache, pos)
        else:
            logits, cache = self.model.decode_step(
                params, tok[:, None], cache, pos)
        return logits[:, 0].astype(jnp.float32), cache

    def prefill_batched(self, params, toks, cache):
        # prefill always starts from an empty (or rewound-to-0) cache, so
        # the chunk's rope/mask offset is 0 on both implementations
        if self._decoder is not None:
            logits, cache = self._decoder.forward_chunk(params, toks, cache, 0)
        else:
            logits, cache = self.model.prefill(params, {"tokens": toks}, cache)
        return logits[:, -1].astype(jnp.float32), cache

    def set_pos(self, cache, pos: int):
        """Rewind every block's write cursor — the zero-copy prefix rewind."""
        def leaf(path, x):
            if path and getattr(path[-1], "key", None) == "pos":
                return jnp.full_like(x, pos)   # keeps stacked (reps,) shape
            return x
        return jax.tree_util.tree_map_with_path(leaf, cache)


@dataclasses.dataclass(frozen=True)
class LSTMDraftAdapter:
    """``LSTMModel`` (the paper's §4.2 text draft) as draft substrate.

    The "cache" is the recurrent state stacked ``(layers, B, hidden)`` for
    h and c. Stepping is inherently single-token, so prefill and decode
    share one code path and the oracle equivalence is exact by
    construction.
    """

    model: Any                       # repro.models.LSTMModel

    positional = False
    # recurrent stepping IS the batched prefill: bit-exact by construction
    exact_batched_prefill = True

    def init_cache(self, batch: int, max_len: int):
        cfg = self.model.cfg
        shape = (cfg.num_layers, batch, cfg.hidden)
        # two buffers: the cache is donated, and XLA refuses one buffer
        # donated twice
        return {"h": jnp.zeros(shape, jnp.float32),
                "c": jnp.zeros(shape, jnp.float32)}

    def _unstack(self, cache):
        n = self.model.cfg.num_layers
        return [(cache["h"][i], cache["c"][i]) for i in range(n)]

    def _stack(self, state):
        return {"h": jnp.stack([h for h, _ in state]),
                "c": jnp.stack([c for _, c in state])}

    def decode_step(self, params, tok, cache, pos):
        del pos
        logits, state = self.model.step(params, tok, self._unstack(cache))
        return logits.astype(jnp.float32), self._stack(state)

    def prefill_batched(self, params, toks, cache):
        # recurrent stepping IS the batched prefill (scan over tokens)
        def body(c, tok):
            logits, c = self.decode_step(params, tok, c, 0)
            return c, logits
        cache, logits = jax.lax.scan(body, cache, jnp.moveaxis(toks, 1, 0))
        return logits[-1], cache


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DraftEngineStats:
    """Lifetime counters (prefill skips are the cache-reuse win)."""

    prefill_computes: int = 0
    prefill_reuses: int = 0
    decode_dispatches: int = 0
    tokens_generated: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class _PoolEntry:
    prefix_key: Tuple[bytes, int]    # (prompt fingerprint, prefix_len)
    snapshot: Any                    # post-prefill cache
    logits0: jax.Array               # (B, V) next-token logits after prefix


class ARDraftEngine:
    """Row-keyed KV-cached AR draft generator.

    ``generate_rows(keys (B,) typed PRNG keys, seq_len) -> (B, seq_len)``
    conforms to the scheduler draft contract
    (:mod:`repro.serving.drafts`): row ``b`` depends only on ``keys[b]``.

    Args:
      adapter: :class:`TransformerDraftAdapter` or :class:`LSTMDraftAdapter`.
      params: substrate model parameters.
      max_len: cache capacity — must cover ``prefix_len + seq_len`` of the
        largest request bucket served.
      temperature: sampling temperature.
      bos: prompt used when ``generate_rows`` is called without one.
      prefill_mode: "scan" (single-token prompt replay, bit-exact on any
        adapter), "batched" (one multi-token prefill dispatch; bit-exact
        iff ``adapter.exact_batched_prefill``), or None (default) to pick
        "batched" when the adapter advertises exactness, else "scan".
    """

    def __init__(self, adapter, params, *, max_len: int,
                 temperature: float = 1.0, bos: int = 0,
                 prefill_mode: Optional[str] = None):
        if prefill_mode is None:
            prefill_mode = ("batched"
                            if getattr(adapter, "exact_batched_prefill", False)
                            else "scan")
        if prefill_mode not in ("scan", "batched"):
            raise ValueError(f"prefill_mode must be scan|batched, got {prefill_mode}")
        self.adapter = adapter
        self.params = params
        self.max_len = max_len
        self.temperature = temperature
        self.bos = bos
        self.prefill_mode = prefill_mode
        self.stats = DraftEngineStats()
        self._pool: Dict[int, _PoolEntry] = {}

        adapter_ = adapter
        temp = float(temperature)
        # donation: the cache buffer is dead in the caller after each
        # dispatch — hand it to XLA for in-place reuse (no-op on CPU).
        donate = () if jax.default_backend() == "cpu" else (1,)

        def prefill_scan(params, cache, toks):
            """Consume (B, P) prompt single-token-at-a-time (bit-exact)."""
            def body(c, inp):
                tok, pos = inp
                logits, c = adapter_.decode_step(params, tok, c, pos)
                return c, logits
            p = toks.shape[1]
            cache, logits = jax.lax.scan(
                body, cache,
                (jnp.moveaxis(toks, 1, 0), jnp.arange(p, dtype=jnp.int32)))
            return logits[-1], cache

        def prefill_batched(params, cache, toks):
            return adapter_.prefill_batched(params, toks, cache)

        def decode(params, cache, logits0, keys, start, n_steps):
            """Sample n_steps tokens in ONE scan dispatch.

            Token i is drawn from the carried logits with the row's own
            key folded with i (pack/bucket-invariant); the substrate then
            advances one position. The final token needs no trailing model
            evaluation, so the scan runs n_steps - 1 decode_steps.
            """
            def sample(step_keys, logits):
                return jax.vmap(
                    lambda k, lg: jax.random.categorical(k, lg / temp)
                )(step_keys, logits).astype(jnp.int32)

            fold = jax.vmap(jax.random.fold_in, in_axes=(0, None))

            def body(carry, i):
                logits, cache = carry
                tok = sample(fold(keys, i), logits)
                logits, cache = adapter_.decode_step(
                    params, tok, cache, start + i)
                return (logits, cache), tok

            (last_logits, cache), toks = jax.lax.scan(
                body, (logits0, cache),
                jnp.arange(n_steps - 1, dtype=jnp.int32))
            last = sample(
                fold(keys, jnp.asarray(n_steps - 1, jnp.int32)), last_logits)
            toks = jnp.concatenate(
                [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
            return toks, cache

        self._prefill_scan = jax.jit(prefill_scan, donate_argnums=donate)
        self._prefill_batched = jax.jit(prefill_batched, donate_argnums=donate)
        self._decode = jax.jit(decode, static_argnums=(5,),
                               donate_argnums=donate)

    # ---- prefix bookkeeping ---------------------------------------------

    def _fingerprint(self, prompt: np.ndarray) -> Tuple[bytes, int]:
        a = np.ascontiguousarray(np.asarray(prompt, np.int32))
        return (hashlib.sha1(a.tobytes()).digest(), a.shape[1])

    def _prefix_cache(self, b: int, prompt: jax.Array, key: Tuple[bytes, int]):
        """Post-prefill (cache, logits0) — reused when the pool already
        holds this (rows, prefix); recomputed (into the recycled pooled
        buffer, donated) otherwise.

        Positional adapters: the entry is POPPED — its buffer is about to
        be donated into the decode dispatch, and generate_rows re-pools
        the returned buffer (prefix rewound) afterwards. A failure between
        the two can therefore never leave a donated-away cache in the
        pool; the next call just re-prefills.
        """
        entry = (self._pool.pop(b, None) if self.adapter.positional
                 else self._pool.get(b))
        if entry is not None and entry.prefix_key == key:
            self.stats.prefill_reuses += 1
            return entry.snapshot, entry.logits0

        if entry is not None and self.adapter.positional:
            cache = self.adapter.set_pos(entry.snapshot, 0)  # recycle buffer
        else:
            cache = self.adapter.init_cache(b, self.max_len)
        prefill = (self._prefill_scan if self.prefill_mode == "scan"
                   else self._prefill_batched)
        logits0, cache = prefill(self.params, cache, prompt)
        self.stats.prefill_computes += 1
        if not self.adapter.positional:
            self._pool[b] = _PoolEntry(key, cache, logits0)
        return cache, logits0

    # ---- generation ------------------------------------------------------

    def generate_rows(self, keys: jax.Array, seq_len: int,
                      prompt: Optional[jax.Array] = None) -> jax.Array:
        """Row-keyed draft generation (the scheduler draft contract).

        Args:
          keys: (B,) typed PRNG keys, one per row.
          seq_len: tokens to generate (static; compiles once per
            (rows, seq_len)).
          prompt: optional (B, P) int32 shared prefix; defaults to a
            single-BOS column. The prefix KV survives in the pool, so
            consecutive micro-batches with the same (rows, prompt) skip
            the prefill dispatch entirely.
        Returns:
          (B, seq_len) int32 draft tokens (prompt not included).
        """
        if seq_len < 1:
            raise ValueError(f"seq_len must be >= 1, got {seq_len}")
        b = keys.shape[0]
        if prompt is None:
            prompt = jnp.full((b, 1), self.bos, jnp.int32)
        prompt = jnp.asarray(prompt, jnp.int32)
        if prompt.shape[0] != b:
            raise ValueError(
                f"prompt rows {prompt.shape[0]} != key rows {b}")
        p = prompt.shape[1]
        if p + seq_len - 1 > self.max_len:
            raise ValueError(
                f"prefix {p} + seq_len {seq_len} - 1 exceeds cache capacity "
                f"max_len={self.max_len}")

        fp = self._fingerprint(prompt)
        cache, logits0 = self._prefix_cache(b, prompt, fp)
        if self.adapter.positional:
            # decode consumes (and donates) the pooled buffer; the prefix
            # KV rows < p are never overwritten, so afterwards a pos
            # rewind restores the snapshot with zero copies.
            decode_cache = cache
        else:
            decode_cache = jax.tree.map(jnp.copy, cache)
        toks, cache_out = self._decode(
            self.params, decode_cache, logits0, keys,
            jnp.asarray(p, jnp.int32), int(seq_len))
        if self.adapter.positional:
            self._pool[b] = _PoolEntry(fp, self.adapter.set_pos(cache_out, p),
                                       logits0)
        self.stats.decode_dispatches += 1
        self.stats.tokens_generated += b * seq_len
        return toks

    def as_draft_fn(self) -> Callable[[jax.Array, int], jax.Array]:
        """The scheduler's ``draft_fn(keys, seq_len)`` entry point."""
        return self.generate_rows

    def reset(self) -> None:
        """Drop pooled prefix caches (frees device buffers)."""
        self._pool.clear()
