"""Continuous-batching scheduler tests: end-to-end pipeline, packing
invariance of request outputs, overlap on/off equivalence, per-bucket jit
cache accounting, guarantees, and the (trivial) mesh path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import guarantees
from repro.core.guarantees import GuaranteeViolation
from repro.serving import ServeRequest, WarmStartScheduler, uniform_draft


class ToyFlow:
    """Constant peaked logits; counts python traces of the backbone."""

    def __init__(self, vocab=11, mode=2):
        self.vocab = vocab
        self.mode = mode
        self.trace_calls = []

    def dfm_apply(self, params, x, t, extras=None):
        self.trace_calls.append(1)
        return jnp.zeros(x.shape + (self.vocab,)).at[..., self.mode].set(30.0)


def make_scheduler(**kw):
    flow = ToyFlow()
    sched = WarmStartScheduler(
        flow_model=flow, flow_params={},
        draft_fn=kw.pop("draft_fn", uniform_draft(11)),
        cold_nfe=kw.pop("cold_nfe", 20),
        default_t0=kw.pop("default_t0", 0.8), **kw)
    return sched, flow


def test_end_to_end_mixed_stream():
    sched, flow = make_scheduler(max_rows=8)
    ids = {}
    for L, n, s in [(5, 2, 1), (12, 3, 2), (8, 1, 3), (30, 4, 4)]:
        ids[sched.submit(seq_len=L, num_samples=n, seed=s)] = (L, n)
    results, report = sched.run()
    assert set(results) == set(ids)
    for rid, (L, n) in ids.items():
        r = results[rid]
        assert r.tokens.shape == (n, L)
        assert r.nfe == guarantees.warm_nfe(20, 0.8)
        # peaked logits: the final step lands on pure p1
        assert bool((r.tokens == flow.mode).all())
    assert report["num_requests"] == 4
    assert report["jit_cache"]["misses"] == report["num_micro_batches"]
    assert report["draft_time_s"] > 0 and report["flow_time_s"] > 0
    # queue drained
    assert sched.run()[1]["num_requests"] == 0


def test_output_invariant_to_micro_batch_packing():
    """The determinism contract: same (seq_len, num_samples, seed) request
    gives identical tokens whether served alone, packed with neighbours,
    or split differently by max_rows."""
    outs = []
    for extra, max_rows in [([], 8), ([(9, 2, 77), (6, 1, 88)], 8),
                            ([(12, 4, 99)], 4)]:
        sched, _ = make_scheduler(max_rows=max_rows)
        rid = sched.submit(seq_len=12, num_samples=3, seed=5)
        for L, n, s in extra:
            sched.submit(seq_len=L, num_samples=n, seed=s)
        results, _ = sched.run()
        outs.append(results[rid].tokens)
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


@pytest.mark.slow
def test_overlap_off_matches_overlap_on():
    def stream(sched):
        for L, n, s in [(8, 2, 1), (16, 3, 2), (24, 1, 3), (8, 2, 4)]:
            sched.submit(seq_len=L, num_samples=n, seed=s)
        return sched.run()

    s_on, _ = make_scheduler(overlap=True)
    s_off, _ = make_scheduler(overlap=False)
    res_on, rep_on = stream(s_on)
    res_off, rep_off = stream(s_off)
    assert rep_on["overlap"] and not rep_off["overlap"]
    for rid in res_on:
        np.testing.assert_array_equal(res_on[rid].tokens, res_off[rid].tokens)


def test_jit_cache_hits_across_runs_and_no_shape_retrace():
    sched, flow = make_scheduler()
    sched.submit(seq_len=12, num_samples=2, seed=1)   # bucket 16
    sched.run()
    misses = sched._cache_misses
    n_traces = len(flow.trace_calls)
    # same bucket/rows/nfe -> cache hit, no python retrace of the backbone
    sched.submit(seq_len=13, num_samples=2, seed=9)   # also bucket 16
    _, rep = sched.run()
    assert sched._cache_misses == misses
    assert rep["jit_cache"]["hits"] >= 1
    assert len(flow.trace_calls) == n_traces


def test_t0_override_changes_nfe_and_is_guaranteed():
    sched, _ = make_scheduler()
    a = sched.submit(seq_len=8, seed=1)               # t0=0.8 -> 4 steps
    b = sched.submit(seq_len=8, seed=2, t0=0.5)       # -> 10 steps
    results, _ = sched.run()
    assert results[a].nfe == 4 and results[b].nfe == 10


def test_bucket_guarantee_violation_names_bucket():
    with pytest.raises(GuaranteeViolation, match=r"bucket_len=16 rows=3"):
        guarantees.require_bucket_guarantee(20, 0.8, 7, bucket_len=16, rows=3)


def test_mesh_path_matches_no_mesh_bit_identical():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    outs = []
    for m in (None, mesh):
        sched, _ = make_scheduler(mesh=m)
        rid = sched.submit(seq_len=12, num_samples=3, seed=5)
        results, rep = sched.run()
        outs.append(results[rid].tokens)
        if m is not None:
            assert rep["mesh"] == {"data": 1, "model": 1}
    np.testing.assert_array_equal(outs[0], outs[1])


def test_shared_loop_builder_is_the_core_one():
    """Sampler, server and scheduler consume the ONE scan body from
    core/sampler.py — no duplicated refine loops."""
    from repro.core import sampler as core_sampler
    from repro.serving import engine, scheduler

    assert engine.scan_refine_loop is core_sampler.scan_refine_loop
    assert scheduler.scan_refine_loop is core_sampler.scan_refine_loop
    assert engine.make_euler_one_step is core_sampler.make_euler_one_step
    assert scheduler.make_euler_one_step_rows is core_sampler.make_euler_one_step_rows


def test_row_keyed_sampling_is_row_independent():
    """categorical_from_probs_rows: a row's draw depends only on its own
    key — swapping neighbour rows does not change it."""
    from repro.core.sampler import categorical_from_probs_rows

    keys = jax.random.split(jax.random.key(0), 4)
    probs = jax.random.uniform(jax.random.key(1), (4, 6, 9))
    out = categorical_from_probs_rows(keys, probs)
    perm = jnp.array([2, 0, 3, 1])
    out_perm = categorical_from_probs_rows(keys[perm], probs[perm])
    np.testing.assert_array_equal(np.asarray(out)[np.asarray(perm)],
                                  np.asarray(out_perm))


def test_submit_rejects_unservable_requests_without_poisoning_queue():
    sched, _ = make_scheduler(max_rows=8, max_bucket=32)
    ok = sched.submit(seq_len=12, seed=1)
    with pytest.raises(ValueError):
        sched.submit(seq_len=40)                  # bucket 64 > max_bucket 32
    with pytest.raises(ValueError):
        sched.submit(seq_len=8, num_samples=9)    # > max_rows
    results, _ = sched.run()                      # good request still served
    assert set(results) == {ok}


def test_jit_cache_counts_are_per_run():
    sched, _ = make_scheduler()
    sched.submit(seq_len=12, seed=1)
    _, rep1 = sched.run()
    sched.submit(seq_len=12, seed=2)
    _, rep2 = sched.run()
    assert (rep1["jit_cache"]["hits"], rep1["jit_cache"]["misses"]) == (0, 1)
    assert (rep2["jit_cache"]["hits"], rep2["jit_cache"]["misses"]) == (1, 0)
    # per-compile-key breakdown: the run's one key flips miss -> hit
    (key1, pk1), = rep1["jit_cache"]["per_key"].items()
    (key2, pk2), = rep2["jit_cache"]["per_key"].items()
    assert key1 == key2
    assert pk1 == {"hits": 0, "misses": 1}
    assert pk2 == {"hits": 1, "misses": 0}
    # unfused scheduler dispatches no fused blocks
    assert rep1["jit_cache"]["fused"] == {
        "fused_block": 1, "blocks_dispatched": 0, "steps_fused": 0}


@pytest.mark.parametrize("backend,want", [
    ("cpu", {64: "xla", 128: "xla"}), ("tpu", {64: "xla", 128: "fused"})])
def test_jit_cache_names_the_attention_each_refine_key_took(
        monkeypatch, backend, want):
    """Each refine key's ``per_key`` entry says which attention its trace
    took: on a TPU the fused kernel from 128 tokens, ``_sdpa`` below (the
    rule is told the backend; the kernel runs in interpret mode here)."""
    import functools
    from repro.configs.dfm_dit import tiny_config
    from repro.models import attention, build_model
    cfg = tiny_config(vocab_size=11, seq_len=128).replace(
        num_layers=1, d_model=64, num_heads=2, num_kv_heads=2, d_ff=128)
    model = build_model(cfg)
    monkeypatch.setattr(attention, "attention_impl", functools.partial(
        attention.attention_impl, backend=backend))
    sched = WarmStartScheduler(
        flow_model=model, flow_params=model.init(jax.random.key(0)),
        draft_fn=uniform_draft(11), cold_nfe=10, default_t0=0.8,
        max_rows=4, max_bucket=128)
    sched.submit(seq_len=40, seed=1)
    sched.submit(seq_len=100, seed=2)
    _, rep = sched.run()
    per_key = rep["jit_cache"]["per_key"]
    assert {int(k.strip("()").split(",")[0]): e["attention"]
            for k, e in per_key.items()} == want
    sched.submit(seq_len=100, seed=3)  # a hit: no trace, the record stays
    _, rep2 = sched.run()
    (entry,) = rep2["jit_cache"]["per_key"].values()
    assert entry == {"hits": 1, "misses": 0, "attention": want[128]}
