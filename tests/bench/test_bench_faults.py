"""The correctness check sees each fault a served cell can have, planted
underneath a CPU rehearsal run of the benchmark (the chip look skipped):
a refine step that returns its state unchanged, half of the rows of each
micro-batch left unrefined, tokens altered where the refine produces them (every 8th
of each row), and a draft token altered where the draft engine produces
it. The exchange between
chips does not exist in a one-chip cell."""

import jax.numpy as jnp
import pytest

from bench import harness
from tests_bench_tiny import CELLS, SIZES, run


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def plant(monkeypatch, fault, vocab):
    import repro.serving.scheduler as sched
    from repro.drafting import ar_engine

    if fault == "state_unchanged":
        monkeypatch.setattr(sched, "make_euler_one_step_rows",
                            lambda *a, **k: (lambda keys, lg, x, t, h: x))
        return
    if fault == "draft_token":
        orig = ar_engine.ARDraftEngine.generate_rows

        def generate_rows(self, keys, seq_len, prompt=None):
            x = orig(self, keys, seq_len, prompt)
            return x.at[:, seq_len // 2].set((x[:, seq_len // 2] + 1) % vocab)

        monkeypatch.setattr(ar_engine.ARDraftEngine, "generate_rows",
                            generate_rows)
        return
    orig_loop = sched.scan_refine_loop_rows

    def loop(logits_fn, one_step, x_init, *a, **k):
        out = orig_loop(logits_fn, one_step, x_init, *a, **k)
        if fault == "half_batch":
            # every other row (the first of each micro-batch among them)
            # comes back unrefined
            keep = (jnp.arange(x_init.shape[0]) % 2 == 1)[:, None]
            return jnp.where(keep, out, x_init)
        # token_altered: every 8th token of every row
        return out.at[:, ::8].set((out[:, ::8] + 1) % vocab)

    monkeypatch.setattr(sched, "scan_refine_loop_rows", loop)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "clear_mismatch"),
    ("half_batch", "clear_mismatch"),
    ("token_altered", "clear_mismatch"),
    ("draft_token", "draft_gap"),
])
def test_fault_makes_correct_false(monkeypatch, cell, fault, caught_by):
    vocab = SIZES[cell]["config"]["model"].get(
        "vocab_size", harness.cell_spec(cell).config["model"]["vocab_size"])
    plant(monkeypatch, fault, vocab)
    r = run(cell, seed=2 ** 33 + 17, seconds=1.0)
    assert r["correct"] is False
    c = r["checks"][caught_by]
    assert c["value"] > c["limit"], r["checks"]
