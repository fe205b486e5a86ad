"""bench/flops.py against XLA's own operation count, and the peaks
table's refusal of an unknown device."""

import jax
import jax.numpy as jnp
import pytest

from bench import archs, flops, harness, weights

# flops.py counts matmul multiply-adds only; XLA's count also has the
# norms, activations, softmax and residual adds: a few operations per
# element against 2 * width per matmul output. At the widths below they
# are 2-6% of the total, so flops.py must lie in [0.90, 1.0] of XLA's.
# One layer: XLA's cost analysis counts a scanned layer stack's body once.
TOLERANCE = (0.90, 1.0)


def tiny(config):
    m = harness._json(harness.BENCH / "configs" / f"{config}.json")["model"]
    return dict(m, num_hidden_layers=1, hidden_size=256,
                num_attention_heads=4,
                num_key_value_heads=(4 if m["num_key_value_heads"] ==
                                     m["num_attention_heads"] else 2),
                head_dim=64, intermediate_size=1024, time_embed_dim=32,
                vocab_size=min(m["vocab_size"], 512),
                weight_dtype="float32", activation_dtype="float32")


@pytest.mark.parametrize("config", ["dfm-dit", "starcoder2-3b"])
@pytest.mark.parametrize("rows,seq", [(2, 64), (4, 128)])
def test_forward_flops_match_xla(config, rows, seq):
    from repro.models import build_model

    m = tiny(config)
    arch = archs.load(m["architecture"])
    model = build_model(arch.model_config(config, m))
    w = weights.make(0, arch.shapes(m), "float32", 0)
    params = arch.to_program(w, model, jax.random.key(0))
    tokens = jnp.zeros((rows, seq), jnp.int32)
    t = jnp.full((rows,), 0.9, jnp.float32)
    cost = jax.jit(model.dfm_apply).lower(params, tokens, t).compile() \
        .cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ratio = flops.forward_flops(m, rows, seq) / cost["flops"]
    assert TOLERANCE[0] <= ratio <= TOLERANCE[1], ratio


def test_param_count_matches_the_weights():
    for config in ("dfm-dit", "starcoder2-3b"):
        m = tiny(config)
        shapes = archs.load(m["architecture"]).shapes(m)
        n = sum(int(jnp.prod(jnp.asarray(s))) for s in shapes.values())
        assert flops.param_count(m) == n


def test_roofline_takes_the_larger_bound():
    m = tiny("dfm-dit")
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e20}
    assert flops.roofline_s(m, 2, 64, peak) == pytest.approx(
        flops.forward_flops(m, 2, 64) / 1e12)
    peak = {"bf16_flops_per_s": 1e30, "hbm_bytes_per_s": 1e9}
    assert flops.roofline_s(m, 2, 64, peak) == pytest.approx(
        flops.forward_bytes(m, 2, 64) / 1e9)


def test_peaks_refuse_an_unknown_device():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
