"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

``extract`` reads an ``.xplane.pb`` into flat events
``(plane, line, name, start_ns, dur_ns)``: every event of the TPU device
planes and the host events the benchmark needs (its own ``bench.*``
``TraceAnnotation`` spans and JAX's dispatch spans). ``reduce`` works on
such a list only, so a small recorded trace kept as JSON checks it.

On a TPU device plane, line ``XLA Ops`` holds one event per operation run
on the chip, and line ``XLA Modules`` one event per program execution,
named ``jit_<function>(<id>)``. The traced window is the host span
``bench.trace_window``.

* busy: the union of operation intervals inside the window, per device,
  averaged over the devices that ran anything;
* per-program device time: module executions, by program name without
  the ``(<id>)`` suffix;
* top operations (leaves: a loop's operation spans its body's) by summed
  device time;
* idle gaps: the longest stretches inside the window with no operation
  on the first device, each named by the host span that covers most of
  it (see ``_what``).
"""

from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

Event = Tuple[str, str, str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.trace_window"
LOOP_SPAN = "bench.serve_stream"
# host spans kept: the benchmark's own, and JAX's jit dispatch spans
HOST_KEEP = re.compile(r"^(bench\.|PjitFunction\()")


def extract(path: str) -> List[Event]:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: List[Event] = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if device or HOST_KEEP.match(e.name):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    return out


def load(path: str) -> List[Event]:
    """Events saved as a JSON list (gzip-compressed when ``.gz``)."""
    with (gzip.open(path, "rt") if path.endswith(".gz") else open(path)) as f:
        return [tuple(e) for e in json.load(f)]


def op_name(name: str) -> str:
    """An operation's event name is its HLO text on a TPU:
    ``%fusion.253 = f32[...] fusion(...)`` -> ``fusion.253``."""
    return name.split(" = ", 1)[0].lstrip("%")


def program_name(module: str) -> str:
    """``jit_refine(1234)`` -> ``jit_refine``."""
    return re.sub(r"\(\d+\)$", "", module)


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _clip(lo, hi, w_lo, w_hi):
    return max(lo, w_lo), min(hi, w_hi)


def window(events: List[Event]) -> Tuple[float, float]:
    spans = [(s, s + d) for p, _, n, s, d in events
             if n == WINDOW_SPAN and not DEVICE_PLANE.match(p)]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _what(host, lo: float, hi: float) -> str:
    """The host span that covers most of an idle gap. The benchmark's
    span around the whole serving loop covers nearly every gap, so it
    names a gap only when no narrower span covers a fifth of it."""
    cover: Dict[str, float] = defaultdict(float)
    for name, s, e in host:
        ov = min(e, hi) - max(s, lo)
        if ov > 0 and name != WINDOW_SPAN:
            cover[name.split("#")[0]] += ov
    narrow = {n: c for n, c in cover.items() if n != LOOP_SPAN}
    if narrow and max(narrow.values()) >= 0.2 * (hi - lo):
        return max(narrow, key=narrow.get)
    return LOOP_SPAN if LOOP_SPAN in cover else "no host span"


def reduce(events: List[Event], *, top: int = 10) -> dict:
    """Seconds of busy device time, per-program device time, the top
    operations and the longest idle gaps inside the traced window."""
    w_lo, w_hi = window(events)
    ops: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    modules: List[Tuple[str, str, float, float]] = []
    host: List[Tuple[str, float, float]] = []
    for plane, line, name, start, dur in events:
        if DEVICE_PLANE.match(plane):
            lo, hi = _clip(start, start + dur, w_lo, w_hi)
            if line == OPS_LINE and hi > lo:
                ops[plane].append((lo, hi, op_name(name)))
            elif line == MODULES_LINE and hi > lo:
                modules.append((plane, program_name(name), start,
                                start + dur))
        else:
            host.append((name, start, start + dur))
    devices = sorted(p for p in ops if ops[p])
    busy_by_dev = {p: union((lo, hi) for lo, hi, _ in ops[p])
                   for p in devices}
    busy_ns = [sum(hi - lo for lo, hi in busy_by_dev[p]) for p in devices]
    # a loop's operation spans the operations of its body: rank leaves only
    op_time: Dict[str, float] = defaultdict(float)
    for p in devices:
        seq = sorted(ops[p])
        for i, (lo, hi, name) in enumerate(seq):
            if i + 1 < len(seq) and seq[i + 1][0] < hi:
                continue
            op_time[name] += (hi - lo) / 1e9
    prog_time: Dict[str, float] = defaultdict(float)
    for plane, name, lo, hi in modules:
        clo, chi = _clip(lo, hi, w_lo, w_hi)
        prog_time[name] += (chi - clo) / 1e9 / max(len(devices), 1)

    gaps = []
    if devices:
        edges = [w_lo] + [x for iv in busy_by_dev[devices[0]] for x in iv] \
            + [w_hi]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                gaps.append((lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_what(host, lo, hi), (hi - lo) / 1e9] for lo, hi in gaps[:top]]

    return {
        "window_s": (w_hi - w_lo) / 1e9,
        "busy_s": (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0,
        "devices": len(devices),
        "program_s": dict(prog_time),
        "executions": sorted((lo, hi, name) for _, name, lo, hi in modules),
        "host_spans": sorted((s, e, name) for name, s, e in host),
        "window": (w_lo, w_hi),
        "device_ops": sorted(([n, t] for n, t in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": idle,
    }


def match_executions(summary: dict, program: str, marker: str):
    """Pair each execution of ``program`` that lies inside the window
    with the first host span named ``<marker>#<k>`` that starts after it
    ends and before the next execution starts: ``[(k, device_s)]``."""
    w_lo, w_hi = summary["window"]
    execs = [(lo, hi) for lo, hi, name in summary["executions"]
             if name == program and lo >= w_lo and hi <= w_hi]
    marks = [(s, int(name.split("#")[1])) for s, _, name
             in summary["host_spans"] if name.startswith(marker + "#")]
    out = []
    for i, (lo, hi) in enumerate(execs):
        nxt = execs[i + 1][0] if i + 1 < len(execs) else float("inf")
        ks = [k for s, k in marks if hi <= s < nxt]
        if ks:
            out.append((ks[0], (hi - lo) / 1e9))
    return out
