"""Substrate tests: optimizers, checkpointing, data pipelines, sharding
rules, HLO analyzer."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.data import (
    NGramProxyLM, SyntheticCorpus, WordOracle, decode, draft_tier_dataset,
    encode, frechet_distance, images_dataset, moons_dataset, symmetric_kl,
)
from repro.optim import Adafactor, AdamW, clip_by_global_norm, warmup_cosine


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def _rosenbrock_ish(params):
    return jnp.sum((params["w"] - 3.0) ** 2) + jnp.sum((params["b"] + 1.0) ** 2)


@pytest.mark.parametrize("opt", [
    AdamW(learning_rate=0.1),
    AdamW(learning_rate=0.1, amsgrad=True),
    AdamW(learning_rate=0.1, amsgrad=True, moments_dtype="bfloat16"),
    Adafactor(learning_rate=0.5),
])
def test_optimizers_decrease_loss(opt):
    params = {"w": jnp.zeros((4, 3)), "b": jnp.zeros((3,))}
    state = opt.init(params)
    l0 = float(_rosenbrock_ish(params))
    for _ in range(60):
        g = jax.grad(_rosenbrock_ish)(params)
        params, state = opt.update(g, state, params)
    assert float(_rosenbrock_ish(params)) < 0.05 * l0


def test_adamw_matches_reference_step():
    """One AdamW step against the textbook update."""
    opt = AdamW(learning_rate=0.1, b1=0.9, b2=0.999, eps=1e-8)
    p = {"w": jnp.array([1.0, 2.0])}
    g = {"w": jnp.array([0.5, -1.0])}
    state = opt.init(p)
    p_new, _ = opt.update(g, state, p)
    m = 0.1 * np.array([0.5, -1.0])
    v = 0.001 * np.array([0.25, 1.0])
    upd = (m / 0.1) / (np.sqrt(v / 0.001) + 1e-8)
    np.testing.assert_allclose(np.asarray(p_new["w"]),
                               np.array([1.0, 2.0]) - 0.1 * upd, rtol=1e-5)


def test_warmup_cosine_shape():
    s = warmup_cosine(1.0, 10, 100)
    assert float(s(jnp.asarray(0))) == pytest.approx(0.0)
    assert float(s(jnp.asarray(10))) == pytest.approx(1.0)
    assert float(s(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-3)
    assert float(s(jnp.asarray(55))) < 1.0


def test_grad_clip():
    g = {"a": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(np.sqrt(1000.0), rel=1e-5)
    assert float(jnp.linalg.norm(clipped["a"])) == pytest.approx(1.0, rel=1e-4)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    from repro.training.state import TrainState
    opt = AdamW(learning_rate=0.1, amsgrad=True)
    params = {"layer": {"w": jnp.arange(6.0).reshape(2, 3)},
              "list": [jnp.ones((2,)), jnp.zeros((3,))]}
    state = TrainState.create(params, opt)
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, state, step=7)
    assert latest_step(d) == 7
    template = TrainState.create(jax.tree.map(jnp.zeros_like, params), opt)
    restored = restore_checkpoint(d, template)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    d = str(tmp_path / "c2")
    save_checkpoint(d, {"w": jnp.ones((2, 2))}, step=1)
    with pytest.raises(ValueError):
        restore_checkpoint(d, {"w": jnp.ones((3, 3))})


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_moons_dataset_and_skl():
    a = moons_dataset(4000, seed=0)
    b = moons_dataset(4000, seed=1)
    assert a.shape == (4000, 2) and a.min() >= 0 and a.max() < 128
    noise = np.random.default_rng(0).integers(0, 128, size=(4000, 2))
    assert symmetric_kl(a, b) < 0.5
    assert symmetric_kl(noise, a) > symmetric_kl(b, a) * 2


def test_draft_tiers_ordering():
    ref = moons_dataset(4000, seed=5)
    skls = {t: symmetric_kl(draft_tier_dataset(4000, t, seed=5), ref)
            for t in ("pretty_good", "fair", "poor")}
    assert skls["pretty_good"] < skls["fair"] < skls["poor"]


def test_text_corpus_and_oracle():
    c = SyntheticCorpus(seed=0)
    seqs = c.sequences(32, 64, seed=1)
    assert seqs.shape == (32, 64) and seqs.max() < 27
    text = decode(seqs[0])
    assert all(ch in " abcdefghijklmnopqrstuvwxyz" for ch in text)
    # oracle maps noisy text to dictionary words
    oracle = WordOracle(c)
    noisy = encode("thx of anq tb in a iz")
    refined = decode(oracle(noisy[None])[0])
    words = [w for w in refined.split() if w]
    assert all(w in c.words for w in words)


def test_ngram_proxy_prefers_real_text():
    c = SyntheticCorpus(seed=0)
    train = c.sequences(256, 64, seed=1)
    proxy = NGramProxyLM(order=3).fit(train)
    real = c.sequences(32, 64, seed=2)
    noise = np.random.default_rng(0).integers(0, 27, size=(32, 64))
    assert proxy.nll(real) < proxy.nll(noise)


def test_images_and_fid():
    a = images_dataset(512, seed=0)
    b = images_dataset(512, seed=1)
    noise = np.random.default_rng(0).integers(0, 256, size=(512, 64))
    assert frechet_distance(a, b) < frechet_distance(noise, a)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_param_specs_on_smoke_model():
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_smoke_config
    from repro.distributed.sharding import TRAIN_RULES, param_specs
    from repro.models import build_model

    cfg = get_smoke_config("starcoder2-3b").replace(
        d_model=128, d_ff=256, vocab_size=512)
    model = build_model(cfg)
    params_abs = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    specs = param_specs(params_abs, TRAIN_RULES, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    assert all(isinstance(s, P) for _, s in flat)
    # every spec's sharded dims divide the param dims (mesh size 1 -> all ok)
    # now with a 2x2 mesh the ffn dims (256) must shard over model=2
    mesh2 = jax.make_mesh((2, 2), ("data", "model")) if len(jax.devices()) >= 4 else None
    if mesh2 is not None:
        specs2 = param_specs(params_abs, TRAIN_RULES, mesh2)


def test_local_mesh_axes_are_auto():
    """The shard rules rely on propagated constraints: a mesh with
    ``Explicit`` axes (``jax.make_mesh``'s default) rejects the model's
    unannotated matmuls over a sharded contraction."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_logical_to_spec_drops_missing_axes():
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import TRAIN_RULES, logical_to_spec
    mesh = jax.make_mesh((1,), ("data",))  # no 'model' or 'pod' axis
    spec = logical_to_spec(("batch", "ffn"), TRAIN_RULES, mesh)
    assert spec == P("data")  # pod dropped from batch, ffn (model) dropped


# ---------------------------------------------------------------------------
# HLO analyzer
# ---------------------------------------------------------------------------

def test_hlo_analyzer_counts_while_multipliers():
    from repro.launch.hlo_analysis import analyze_module
    hlo = """
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %dot.1 = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %c1 = s32[] constant(1)
  %add.1 = s32[] add(%i, %c1)
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%add.1, %dot.1)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[8,8]{1,0}) tuple(%zero, %a)
  %w = (s32[], f32[8,8]{1,0}) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
    st = analyze_module(hlo)
    # 5 iterations x 2*8*8*8 dot flops
    assert st.flops >= 5 * 2 * 8 * 8 * 8
    assert st.flops < 5 * 2 * 8 * 8 * 8 * 1.5


def test_hlo_analyzer_collectives():
    from repro.launch.hlo_analysis import analyze_module
    hlo = """
HloModule test

ENTRY %main (a: f32[16,16]) -> f32[16,16] {
  %a = f32[16,16]{1,0} parameter(0)
  ROOT %ag = f32[16,16]{1,0} all-reduce(%a), replica_groups={}
}
"""
    st = analyze_module(hlo)
    assert st.collective_breakdown.get("all-reduce") == 16 * 16 * 4
