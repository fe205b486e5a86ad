"""CPU rehearsal of ``chip_smoke.py``: its serve and kernel phases run at
the tiny DFM-DiT widths with the Pallas kernels in interpret mode, and
``main()`` refuses to run, printing no result, without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.configs.dfm_dit import tiny_config  # noqa: E402


def _phase_lines(out: str) -> dict:
    lines = [json.loads(line[len("phase "):]) for line in out.splitlines()
             if line.startswith("phase ")]
    return {p["phase"]: p for p in lines}


def test_serve_phase_at_tiny_widths(capsys):
    chip_smoke.serve_phase(tiny_config(vocab_size=27, seq_len=16), seed=0,
                           traffic=((8, 2, 4), (16, 1, 8)), max_rows=8)
    phases = _phase_lines(capsys.readouterr().out)
    assert set(phases) == {"serve_fixed", "serve_adaptive", "backbone_logits"}
    assert phases["serve_fixed"]["statuses"] == {"completed": 3}
    assert sum(phases["serve_adaptive"]["statuses"].values()) == 3
    assert phases["backbone_logits"]["rel_l2"] <= \
        phases["backbone_logits"]["bound"]


def test_kernel_phase_interpret(capsys):
    chip_smoke.kernel_phase(seed=0, interpret=True, hw_prng=False,
                            ws_shapes=((16, 27),), flash_seq=32,
                            draft_shape=(2, 8, 16))
    phases = _phase_lines(capsys.readouterr().out)
    assert phases["kernels"]["interpret"] is True
    assert phases["kernels"]["draft_decode"]["rel_l2_vs_cpu"] <= \
        phases["kernels"]["draft_decode"]["bound"]


def test_main_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_cache_placement(monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins untouched; without it the cache
    is one fixed directory in the checkout."""
    import jax
    from repro.launch.compile_cache import (
        CHECKOUT_CACHE_DIR, enable_compile_cache,
    )
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR.parent == Path(chip_smoke.__file__).parent
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_script_alone_fails_without_the_repo(tmp_path):
    """Copied into a directory that holds nothing else of the repo, the
    script exits non-zero and prints no result."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(Path(chip_smoke.__file__).read_text())
    proc = subprocess.run([sys.executable, str(alone)], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120,
                          env={**os.environ, "PYTHONPATH": ""})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
