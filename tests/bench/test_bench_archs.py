"""The architecture as a plug-in (``bench/archs/<architecture>.py``).

The golden values were taken before the DiT code moved into
``bench/archs/dfm-dit.py``, from ``bench/flops.py``,
``bench/weights.dit_shapes`` and ``bench/reference.dit_logits`` as they
were: the move changes no count, no weight and no reference logit.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs, flops, harness, reference, weights
from test_bench_flops import tiny

CONFIGS = ("dfm-dit", "starcoder2-3b")
CONTRACT = {"model_config", "shapes", "to_program", "logits", "param_count",
            "forward_flops", "forward_bytes"}

PARAMS = {"dfm-dit": 86063104, "starcoder2-3b": 2457905152}
# (rows, seq) -> (forward_flops, forward_bytes) at the published widths
COUNTS = {
    "dfm-dit": {
        (8, 1024): (1701163565056.0, 950001664.0),
        (32, 512): (3093123039232.0, 1555750912.0),
        (1, 64): (11027382272.0, 348984832.0),
    },
    "starcoder2-3b": {
        (8, 1024): (42674849579008.0, 8942342144.0),
        (32, 512): (82875907047424.0, 12968873984.0),
        (1, 64): (315284258816.0, 4947267584.0),
    },
}
# leaf -> (shape, checksum) of weights.make(0, shapes(tiny), "float32", 0)
WEIGHTS = {
    "dfm-dit": {
        "embed": ((27, 256), (112.01569615109338, -0.523562455074742)),
        "final_bias": ((256,), (4.06462813433609, 0.4185364816644841)),
        "final_scale": ((256,), (255.07541245222092, 382.14968558736877)),
        "head": ((256, 27), (346.09460336525444, -1.8223631014351307)),
        "ln1_bias": ((1, 256), (4.294702990206133, -0.253024021283679)),
        "ln1_scale": ((1, 256), (255.85771518945694, 383.5480029812046)),
        "ln2_bias": ((1, 256), (4.251718755429465, 0.57910408005495)),
        "ln2_scale": ((1, 256), (253.36125421524048, 380.5766571944835)),
        "time_w1": ((32, 128), (582.11472769174, -7.537062879160011)),
        "time_w2": ((128, 256), (2321.7909814310206, 3.2542325929228895)),
        "w_down": ((1, 1024, 256), (6547.865202489302, -11.210896977874436)),
        "w_up": ((1, 256, 1024), (13070.11331981936, -90.806996154621)),
        "wk": ((1, 256, 256), (3259.4924517390646, 6.628782170229883)),
        "wo": ((1, 256, 256), (3257.5696925510065, 41.285864999730116)),
        "wq": ((1, 256, 256), (3261.6872817416443, -16.076279001349697)),
        "wv": ((1, 256, 256), (3264.823732404184, -13.631387796225761)),
    },
    "starcoder2-3b": {
        "b_down": ((1, 256), (3.915419299999485, -0.4273577031140745)),
        "b_up": ((1, 1024), (16.13144622992388, 0.7428204107612455)),
        "bk": ((1, 128), (2.290026967355516, 0.22961994880942377)),
        "bo": ((1, 256), (3.9510450813222633, 0.10734818503742827)),
        "bq": ((1, 256), (4.294702990206133, -0.253024021283679)),
        "bv": ((1, 128), (2.202082402232918, 0.09544982239876704)),
        "embed": ((512, 256), (2097.2641861648267, -6.3790959336349715)),
        "final_bias": ((256,), (3.9732145424641203, -0.6846684204344295)),
        "final_scale": ((256,), (255.54553306102753, 383.42434055758457)),
        "ln1_bias": ((1, 256), (4.2025679218932055, 0.056655620348265434)),
        "ln1_scale": ((1, 256), (253.9550682902336, 380.7888483024111)),
        "ln2_bias": ((1, 256), (3.94164194390396, 0.411134341840978)),
        "ln2_scale": ((1, 256), (255.9404609799385, 384.26520441045943)),
        "time_w1": ((32, 128), (590.032372405025, -0.7760556097772575)),
        "time_w2": ((128, 256), (2312.6983278489424, 2.263154868630151)),
        "w_down": ((1, 1024, 256), (6537.063824344742, -15.867367710006455)),
        "w_up": ((1, 256, 1024), (13040.81078585452, -30.779051152090005)),
        "wk": ((1, 256, 128), (1632.4510844665244, 25.626413805501002)),
        "wo": ((1, 256, 256), (3259.495575683911, -38.75153413115643)),
        "wq": ((1, 256, 256), (3252.6989861681946, 24.788897910595274)),
        "wv": ((1, 256, 128), (1627.263006648438, 14.241210566361953)),
    },
}
# (shape, checksum) of the "f32" reference logits on TOKENS at times TIMES
LOGITS = {
    "dfm-dit": ((2, 16, 27), (698.594413095736, 220.1684840277864)),
    "starcoder2-3b": ((2, 16, 512), (4086.4943825346418, 51.00692689735443)),
}
TIMES = (0.3, 0.9)


def tokens(vocab):
    return jnp.asarray((np.arange(32).reshape(2, 16) * 7) % vocab, jnp.int32)


def checksum(x):
    """Sum of |x| and a position-weighted sum: a different draw, order or
    layout of the same values moves the second."""
    x = np.asarray(x, np.float64).ravel()
    return float(np.abs(x).sum()), float(x @ np.linspace(1.0, 2.0, x.size))


def assert_checksum(got, want, rel):
    assert got[0] == pytest.approx(want[0], rel=rel)
    assert abs(got[1] - want[1]) <= rel * want[0], (got, want)


def published(config):
    return harness._json(harness.BENCH / "configs" / f"{config}.json")["model"]


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("rows,seq", [(8, 1024), (32, 512), (1, 64)])
def test_counts_equal_the_parents(config, rows, seq):
    m = published(config)
    assert flops.param_count(m) == PARAMS[config]
    assert (flops.forward_flops(m, rows, seq),
            flops.forward_bytes(m, rows, seq)) == COUNTS[config][(rows, seq)]


@pytest.mark.parametrize("config", CONFIGS)
def test_weights_equal_the_parents(config):
    m = tiny(config)
    w = weights.make(0, archs.load(m["architecture"]).shapes(m), "float32", 0)
    assert sorted(w) == sorted(WEIGHTS[config])
    for name, (shape, sums) in WEIGHTS[config].items():
        assert w[name].shape == shape, name
        assert_checksum(checksum(w[name]), sums, 1e-6)


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_logits_equal_the_parents(config):
    m = tiny(config)
    arch = archs.load(m["architecture"])
    w = weights.make(0, arch.shapes(m), "float32", 0)
    got = arch.logits(w, m, tokens(m["vocab_size"]),
                      jnp.asarray(TIMES, jnp.float32), "f32")
    shape, sums = LOGITS[config]
    assert got.shape == shape
    assert_checksum(checksum(got), sums, 1e-5)


def test_an_unknown_architecture_names_the_file_it_looked_for():
    with pytest.raises(KeyError, match="bench/archs/no-such-arch.py"):
        flops.param_count({"architecture": "no-such-arch"})
    with pytest.raises(KeyError, match="bench/archs/no-such-arch.py"):
        archs.load("no-such-arch")


def test_dfm_dit_defines_the_contract_and_its_reference_needs_no_program():
    mod = archs.load("dfm-dit")
    defined = {n for n, v in vars(mod).items() if callable(v)
               and getattr(v, "__module__", None) == mod.__name__
               and not n.startswith("_")}
    assert defined == CONTRACT
    root = str(harness.ROOT)
    script = textwrap.dedent(f"""
        import json, sys
        sys.path[:] = [{root!r}] + [p for p in sys.path if p != {root!r}]
        import jax.numpy as jnp
        from bench import archs, weights
        m = json.loads(sys.argv[1])
        arch = archs.load("dfm-dit")
        w = weights.make(0, arch.shapes(m), "float32", 0)
        x = arch.logits(w, m, jnp.zeros((1, 8), jnp.int32),
                        jnp.full((1,), 0.5), "f32")
        print(x.shape, sorted(k for k in sys.modules
                              if k.split(".")[0] == "repro"))
    """)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", script, json.dumps(tiny("dfm-dit"))],
        env=env, cwd=root, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "(1, 8, 27) []"


TOY = '''
import jax


def model_config(name, m):
    raise NotImplementedError


def shapes(m):
    return {"bias": (m["vocab_size"],)}


def to_program(w, model, key):
    raise NotImplementedError


def logits(w, m, tokens, t, mode):
    """Every position's next token is its token plus one."""
    v = m["vocab_size"]
    return 50.0 * jax.nn.one_hot((tokens + 1) % v, v) + w["bias"]


def param_count(m):
    return m["vocab_size"]


def forward_flops(m, rows, seq):
    return 123.0 * rows * seq


def forward_bytes(m, rows, seq):
    return 7.0 * rows * seq
'''


def test_an_architecture_added_as_a_new_file_is_used(tmp_path, monkeypatch):
    (tmp_path / "toy-arch.py").write_text(TOY)
    monkeypatch.setattr(archs, "ARCHS", tmp_path)
    m = {"architecture": "toy-arch", "vocab_size": 11}
    assert flops.forward_flops(m, 4, 16) == 123.0 * 4 * 16
    peak = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    assert flops.roofline_s(m, 2, 8, peak) == 123.0 * 2 * 8
    w = {"bias": jnp.zeros((11,), jnp.float32)}
    x = np.arange(12, dtype=np.int32).reshape(2, 6) % 11
    _, fkeys = reference.row_keys([5, 6], [0, 0])
    # one step from t0 = 0 takes the chain wholly to the denoiser's logits
    got, _ = reference.refine_chain(w, m, x, fkeys, 1, 0.0, "f32")
    np.testing.assert_array_equal(np.asarray(got), (x + 1) % 11)
