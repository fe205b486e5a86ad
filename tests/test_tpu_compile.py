"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses what the chip would refuse
(unsupported casts, block shapes off the (8, 128) tiling, too much VMEM).
Interpret-mode tests cannot see any of that. Each test lowers one kernel
with ``interpret=False`` at the main path's widths and checks that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import
time: only one process at a time may load the TPU library, and pytest
workers each import every test file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.dfm_dit import tiny_config
from repro.kernels import DraftDecoder
from repro.kernels.draft_decode.kernel import (
    ROWS, attn_cached_pallas, head_pallas, post_attn_pallas, qkv_rope_pallas,
)
from repro.kernels.flash_attn import flash_attention
from repro.kernels.flash_attn.kernel import heads_per_block, pick_blocks
from repro.kernels.ws_fused import pick_tiles_fused
from repro.kernels.ws_fused.kernel import ws_fused_streamed_pallas
from repro.kernels.ws_step import pick_tiles
from repro.kernels.ws_step.kernel import ws_step_streamed_pallas
from repro.models import build_model
from repro.models.attention import fused_attention

LANE = 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _on(tree, sharding):
    """Shapes of ``tree`` placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


@pytest.mark.parametrize("hw_prng", [False, True], ids=["threefry", "hwprng"])
@pytest.mark.parametrize("rows,vocab", [(8192, 27), (256, 32768)])
def test_ws_step_compiles(one_chip, rows, vocab, hw_prng):
    vp = -(-vocab // LANE) * LANE
    rb, bv = pick_tiles(rows, vp)
    fn = functools.partial(ws_step_streamed_pallas, valid_v=vocab,
                           row_block=rb, vocab_tile=bv, use_hw_prng=hw_prng,
                           interpret=False)
    _compile(fn, one_chip, ((rows, vp), jnp.float32), ((rows, 1), jnp.int32),
             ((rows, 1), jnp.float32), ((2,), jnp.int32))


@pytest.mark.parametrize("hw_prng", [False, True], ids=["threefry", "hwprng"])
@pytest.mark.parametrize("rows,vocab", [(8192, 27), (256, 32768)])
def test_ws_fused_compiles(one_chip, rows, vocab, hw_prng):
    k = 4
    vp = -(-vocab // LANE) * LANE
    rb, bv = pick_tiles_fused(rows, vp, k)
    seeds = (k, 2) if hw_prng else (k, rows, 2)
    fn = functools.partial(ws_fused_streamed_pallas, valid_v=vocab,
                           row_block=rb, vocab_tile=bv, use_hw_prng=hw_prng,
                           interpret=False)
    _compile(fn, one_chip, ((rows, vp), jnp.float32), ((rows, 1), jnp.int32),
             ((k, rows, 1), jnp.float32), (seeds, jnp.int32),
             ((rows, 1), jnp.int32))


def test_flash_attn_bidirectional_compiles(one_chip):
    b, s, h, d = 1, 1024, 12, 64
    fn = functools.partial(flash_attention, causal=False, interpret=False)
    _compile(fn, one_chip, *[((b, s, h, d), jnp.float32)] * 3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("s,h,kh,d", [(1024, 12, 12, 64), (512, 24, 2, 128)],
                         ids=["dfm-dit-1024", "starcoder2-3b-512"])
def test_fused_refine_attention_compiles(one_chip, s, h, kh, d, dtype):
    """The refine's fused call at a cell's largest bucket and 32 rows:
    one key block, heads side by side in the lanes (two of 64 per block,
    or one of 128 with its KV head through the index map), float32
    operands rounded to bf16 as the einsum at default precision does."""
    rows = 32
    hb = heads_per_block(h, d)
    assert pick_blocks(s, s, hb * d, jnp.dtype(dtype).itemsize, hb) == (s, s)
    fn = functools.partial(flash_attention, causal=False,
                           mxu_dtype=jnp.bfloat16, interpret=False)
    _compile(fn, one_chip, ((rows, s, h, d), dtype),
             ((rows, s, kh, d), dtype), ((rows, s, kh, d), dtype))


def test_fused_attention_grad_compiles(one_chip):
    """Training through the fused path: its VJP is ``_sdpa``'s."""
    b, s, h, kh, d = 8, 256, 12, 12, 64

    def loss(q, k, v):
        return fused_attention(q, k, v, d ** -0.5, False).sum()

    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
             ((b, s, h, d), jnp.float32), ((b, s, kh, d), jnp.float32),
             ((b, s, kh, d), jnp.float32))


@pytest.fixture(scope="module")
def draft():
    """A small transformer draft at the tiny DFM-DiT widths (4 layers,
    width 192, 6 heads of 32), its cache covering a 256-token bucket."""
    model = build_model(tiny_config(vocab_size=27, seq_len=256))
    params = jax.eval_shape(model.init, jax.random.key(0))
    return model, params


def _layer0(params):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                        params["stack"]["blocks"]["p0"])


@pytest.mark.parametrize("tokens", [8, 256])
def test_draft_qkv_rope_compiles(one_chip, draft, tokens):
    model, params = draft
    cfg, lp = model.cfg, _layer0(params)

    def fn(x, pos, lp):
        return qkv_rope_pallas(
            x, pos, lp["ln1"], lp["attn"], norm=cfg.norm, eps=cfg.norm_eps,
            use_bias=cfg.use_bias, use_rope=True, theta=cfg.rope_theta,
            heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, interpret=False)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
            (((tokens, cfg.d_model), jnp.float32), ((tokens, 1), jnp.int32))]
    hlo = jax.jit(fn).lower(*args, _on(lp, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch,seq", [(32, 1), (4, 64)])
def test_draft_attn_compiles(one_chip, draft, batch, seq):
    model, _ = draft
    cfg = model.cfg
    t = cfg.max_seq_len
    qd = cfg.num_heads * cfg.head_dim
    kd = cfg.num_kv_heads * cfg.head_dim

    def fn(q, k, v, pos0):
        return attn_cached_pallas(q, k, v, pos0, pos0 + seq,
                                  heads=cfg.num_heads,
                                  kv_heads=cfg.num_kv_heads,
                                  head_dim=cfg.head_dim, interpret=False)

    _compile(fn, one_chip, ((batch, seq, qd), jnp.float32),
             ((batch, t, kd), jnp.float32), ((batch, t, kd), jnp.float32),
             ((), jnp.int32))


def test_draft_post_attn_and_head_compile(one_chip, draft):
    model, params = draft
    cfg, lp = model.cfg, _layer0(params)
    r = 4 * ROWS
    qd = cfg.num_heads * cfg.head_dim

    def post(a, x, lp):
        return post_attn_pallas(a, x, lp["attn"], lp["ln2"], lp["mlp"],
                                norm=cfg.norm, eps=cfg.norm_eps,
                                use_bias=cfg.use_bias, act=cfg.act,
                                interpret=False)

    def head(x, fn_p, w):
        return head_pallas(x, fn_p, w, norm=cfg.norm, eps=cfg.norm_eps,
                           interpret=False)

    sd = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    hlo = jax.jit(post).lower(sd((r, qd)), sd((r, cfg.d_model)),
                              _on(lp, one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo
    hlo = jax.jit(head).lower(
        sd((r, cfg.d_model)), _on(params["final_norm"], one_chip),
        _on(params["head"]["w"], one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_draft_forward_chunk_compiles(one_chip, draft):
    """The whole decode step the AR engine dispatches, all four kernels."""
    model, params = draft
    dec = DraftDecoder(model, interpret=False)
    cache = jax.eval_shape(lambda: model.init_cache(32, 256, jnp.float32))
    toks = jax.ShapeDtypeStruct((32, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    hlo = jax.jit(dec.forward_chunk).lower(
        _on(params, one_chip), toks, _on(cache, one_chip),
        pos).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 4
