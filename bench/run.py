"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, in one process that owns the chips the
cell asks for. Set-up (weights from the seed, every program the cell's
traffic opens, the flush policy's cost model) is timed as ``setup_s``
from the start of this process; then the cell's traffic is served for
``--seconds``. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` traces part of the window with the JAX profiler and prints
its per-layer metrics. Every run checks what the timed path served
against the plain reference; the numbers compared are the last lines on
standard error and the ``checks`` entry of the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``checks``. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, size=None) -> int:
    """``size`` is a test-only override (see ``harness.run_cell``): the
    run goes through every step on any backend, and still prints no
    result without a TPU."""
    args = parse(argv)
    try:
        import jax

        from bench import harness
        import repro  # noqa: F401 — the system under test
    except ImportError as err:
        print(f"bench: cannot import the system under test: {err}",
              file=sys.stderr)
        return 2
    chips = harness.cell_spec(args.workload).cell["chips"]
    devices = jax.devices()
    on_chip = devices[0].platform == "tpu" and len(devices) >= chips
    if not on_chip and size is None:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 1
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START, size=size)
    if not on_chip:
        print("bench: no result without a TPU", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
