"""The cell's control, put in the program's place in a CPU rehearsal
run, makes ``correct`` false: the check can tell a step of precision
below what the configuration states. The control is named in
``bench/limits/<config>.json``: the draft picked by the reference LSTM in
bfloat16, and the refine either served by the program switched to
bfloat16 (DFM-DiT states float32) or by the reference with int8 matmuls
(StarCoder2-3B states bfloat16). On the chip, at the cells' own sizes,
the readings that set the limits are in PERF.md."""

import pytest

from bench import harness
from tests_bench_tiny import CELLS, longer, run


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    control = harness.cell_spec(cell).limits["control"]
    r = run(cell, seed=2 ** 40 + 3, control=control, size=longer(cell))
    assert r["correct"] is False
    # the draft in bfloat16 fails the draft's number
    assert r["checks"]["draft_gap"]["value"] > r["checks"]["draft_gap"]["limit"]
    sound = run(cell, seed=2 ** 40 + 3, size=longer(cell))
    assert sound["correct"] is True, sound["checks"]
