"""One module per architecture, ``bench/archs/<architecture>.py``, chosen
by a configuration's ``model["architecture"]``.

An architecture module defines these names and no others (private
helpers aside):

* ``model_config(name, m)``: the program's ``ModelConfig`` for the
  configuration's ``model`` dict ``m``;
* ``shapes(m)``: leaf name -> shape of the benchmark's own weights
  (drawn by ``bench/weights.make``);
* ``to_program(w, model, key)``: the program's parameter tree holding
  the arrays of ``w``, checked by ``bench/weights.check_tree``;
* ``logits(w, m, tokens, t, mode)``: the plain reference's logits
  (B, N, V) at tokens (B, N) and times t (B,), in a mode of
  ``bench/reference.py``;
* ``param_count(m)``, ``forward_flops(m, rows, seq)`` and
  ``forward_bytes(m, rows, seq)``: one denoiser evaluation's parameters,
  operations and least HBM bytes (``bench/flops.py``).

Only ``model_config`` and ``to_program`` may import the program
(``repro``), and only inside their bodies: the reference and the counts
import nothing of ``src/``. A new architecture is a new file here.
"""

from __future__ import annotations

import importlib.util
from functools import cache
from pathlib import Path

ARCHS = Path(__file__).resolve().parent


def load(name: str):
    """The architecture module ``bench/archs/<name>.py``."""
    return _load(ARCHS / f"{name}.py")


@cache
def _load(path: Path):
    if not path.is_file():
        raise KeyError(f"no architecture module {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_arch_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
