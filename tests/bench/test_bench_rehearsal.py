"""A CPU rehearsal of every cell: set-up, window, trace, metrics and the
correctness check run at a tiny size through the benchmark's own path;
without a TPU the command prints no result and exits non-zero."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, run as bench_run
from tests_bench_tiny import CELLS, SIZES, run

ROOT = harness.ROOT


@pytest.fixture(autouse=True)
def no_persistent_cache(monkeypatch):
    # CPU executables from a shared cache directory are not for tests
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)


def expected(cell, trace):
    spec = harness.cell_spec(cell)
    return {m["name"] for m in (spec.per_layer if trace else spec.end_to_end)}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct_on_cpu(cell):
    r = run(cell)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == expected(cell, False)
    assert r["metrics"]["tokens_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["clear_mismatch"]["value"] == 0.0


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_only_what_a_cpu_trace_holds(cell):
    r = run(cell, seed=5, trace=True)
    assert r["correct"] is True, r["checks"]
    got = set(r["metrics"])
    assert got <= expected(cell, True)
    # no device plane on the CPU: no device metric is made up
    assert not got & {"draft_device_share", "device_idle_share", "mfu",
                      "refine_roofline.poisson", "refine_roofline.saturated"}
    assert r["metrics"]["compiles_in_window"]["value"] == 0
    assert "window_s" in r["device"] and "breakdown" in r


def test_main_prints_no_result_without_a_tpu(capsys):
    cell = CELLS[0]
    argv = ["--workload", cell, "--seed", "3", "--seconds", "1",
            "--trace", "0"]
    assert bench_run.main(argv) == 1
    assert bench_run.main(argv, size=SIZES[cell]) == 1
    out = capsys.readouterr().out
    assert "{" not in out


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_command_refuses_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for path in spec["paths"]:
        shutil.copytree(ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
