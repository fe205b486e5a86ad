"""The program's own spans and scopes in a ``jax.profiler`` trace: what the
serving loop was doing while the device sat idle, and which named scope
of the refine program the device time went to.

Given ``SpanTracer(profiler=True)``, the scheduler puts its spans on the
profiler's host plane as ``serve.<stage>`` or ``serve.<stage>#<k>`` (``k``
the micro-batch): ``wait``, ``flush``, ``draft_wait``, ``refine`` (around
``dispatch``) and ``complete`` on the serving loop's thread, ``draft`` on
the draft worker's. The refine scan's operations carry the ``named_scope``
``backbone`` or ``sample_step`` in their op metadata. A TPU v5e's
operation events carry no op metadata (their stats are
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``), so an operation's scope comes from the compiled program's
text, by operation name (``hlo_scopes``, ``rescope``).

The readers work on events ``(plane, line, name, start_ns, dur_ns,
scope)``: the first five as in ``trace_reduce``, and ``scope`` the scope
of a device operation (``"sample_step"``, ``"backbone"``, ``""`` for any
other, None where the program text does not say), ``""`` elsewhere.
``trace_reduce`` keeps neither the ``serve.*`` spans nor the scopes yet,
and no metric reader calls this module yet (``PERF.md``, Open questions).
"""

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace_reduce

SpanEvent = Tuple[str, str, str, float, float, Optional[str]]

SERVE = "serve."
WAIT = "serve.wait"
DRAFT = "serve.draft"          # the draft worker's thread
DISPATCH = "serve.dispatch"
SCOPES = ("sample_step", "backbone")
REFINE_PROGRAM = "jit_refine"
HLO_LINE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*'
                      r'metadata=\{[^}]*op_name="([^"]*)"')


def stage(name: str) -> str:
    """``serve.refine#12`` -> ``serve.refine``."""
    return name.split("#", 1)[0]


def scope_of(op_path: str) -> str:
    """The named scope of an op's metadata path
    (``jit(refine)/while/body/closed_call/sample_step/reduce``)."""
    parts = op_path.split("/")
    return next((s for s in SCOPES if s in parts), "")


def hlo_scopes(texts: List[str]) -> Dict[str, Optional[str]]:
    """Op name -> scope from the text of compiled programs; a name whose
    scope differs between the programs maps to None."""
    out: Dict[str, Optional[str]] = {}
    for text in texts:
        for line in text.splitlines():
            m = HLO_LINE.match(line)
            if m:
                name, scope = m.group(1), scope_of(m.group(2))
                out[name] = scope if out.get(name, scope) == scope else None
    return out


# ---------------------------------------------------------------------------
# readings on an event list
# ---------------------------------------------------------------------------

def _device(events: List[SpanEvent]) -> Optional[str]:
    planes = sorted({p for p, l, *_ in events
                     if trace_reduce.DEVICE_PLANE.match(p)
                     and l == trace_reduce.OPS_LINE})
    return planes[0] if planes else None


def _window(events: List[SpanEvent]) -> Tuple[float, float]:
    spans = [(s, s + d) for p, _, n, s, d, _ in events
             if n == trace_reduce.WINDOW_SPAN
             and not trace_reduce.DEVICE_PLANE.match(p)]
    if not spans:
        raise ValueError(f"trace has no {trace_reduce.WINDOW_SPAN} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _idle(events: List[SpanEvent]):
    """The window and the stretches inside it with no operation on the
    first device."""
    w_lo, w_hi = _window(events)
    dev = _device(events)
    busy = trace_reduce.union(
        trace_reduce._clip(s, s + d, w_lo, w_hi)
        for p, l, _, s, d, _ in events
        if p == dev and l == trace_reduce.OPS_LINE)
    edges = [w_lo] + [x for iv in busy for x in iv] + [w_hi]
    gaps = [(lo, hi) for lo, hi in zip(edges[0::2], edges[1::2]) if hi > lo]
    return (w_lo, w_hi), gaps


def _serve_spans(events: List[SpanEvent]):
    return [(s, s + d, n) for p, _, n, s, d, _ in events
            if n.startswith(SERVE) and not trace_reduce.DEVICE_PLANE.match(p)]


def _innermost(spans) -> List[Tuple[float, float, str]]:
    """Spans ``(lo, hi, name)`` that nest or follow each other -> the
    stretches between their edges, each named by the shortest span
    covering it, in time order."""
    points = sorted({x for lo, hi, _ in spans for x in (lo, hi)})
    out = []
    for a, b in zip(points, points[1:]):
        cover = [(hi - lo, name) for lo, hi, name in spans if lo <= a < hi]
        if cover:
            out.append((a, b, min(cover)[1]))
    return out


def idle_by_stage(events: List[SpanEvent], idle=None) -> Dict[str, float]:
    """Seconds of device idle inside the window under each of the serving
    loop's stages (the innermost ``serve.*`` span of the loop's thread
    that covers it), and ``"outside"`` for idle under none (the loop
    suspended in its caller, or not yet started). ``idle`` is
    ``_idle(events)`` where the caller has it."""
    (w_lo, w_hi), gaps = idle or _idle(events)
    stretches = _innermost([(lo, hi, stage(n))
                            for lo, hi, n in _serve_spans(events)
                            if stage(n) != DRAFT and hi > w_lo and lo < w_hi])
    out: Dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(gaps) and j < len(stretches):
        lo = max(gaps[i][0], stretches[j][0])
        hi = min(gaps[i][1], stretches[j][1])
        if hi > lo:
            out[stretches[j][2]] += (hi - lo) / 1e9
        if gaps[i][1] < stretches[j][1]:
            i += 1
        else:
            j += 1
    outside = sum(hi - lo for lo, hi in gaps) / 1e9 - sum(out.values())
    if outside > 1e-12:
        out["outside"] = outside
    return dict(out)


def host_idle_share(events: List[SpanEvent], idle=None,
                    by: Optional[Dict[str, float]] = None) -> Optional[float]:
    """Share (%) of the traced window in which the device is idle while
    the serving loop is busy on the host: inside a ``serve.*`` span of
    its thread other than ``serve.wait``. None without program spans.
    ``by`` is ``idle_by_stage(events)`` where the caller has it."""
    if not _serve_spans(events) or _device(events) is None:
        return None
    idle = idle or _idle(events)
    by = idle_by_stage(events, idle) if by is None else by
    host = sum(s for name, s in by.items() if name not in (WAIT, "outside"))
    (w_lo, w_hi), _ = idle
    return 100.0 * host / ((w_hi - w_lo) / 1e9)


def refine_ops(events: List[SpanEvent], program: str = REFINE_PROGRAM,
               ) -> Dict[Tuple[str, Optional[str]], float]:
    """Seconds of leaf operation time (a loop's operation spans its
    body's) inside the window's ``program`` executions, by (operation
    name, scope); a scope of None is not known."""
    w_lo, w_hi = _window(events)
    execs = defaultdict(list)
    ops = defaultdict(list)
    for p, l, n, s, d, scope in events:
        if not trace_reduce.DEVICE_PLANE.match(p):
            continue
        lo, hi = trace_reduce._clip(s, s + d, w_lo, w_hi)
        if hi <= lo:
            continue
        if l == trace_reduce.MODULES_LINE and \
                trace_reduce.program_name(n) == program:
            execs[p].append((lo, hi))
        elif l == trace_reduce.OPS_LINE:
            ops[p].append((lo, hi, trace_reduce.op_name(n), scope))
    out: Dict[Tuple[str, Optional[str]], float] = defaultdict(float)
    for p, runs in execs.items():
        runs.sort()
        starts = [a for a, _ in runs]
        # a loop's operation spans its body's: only leaves count (the
        # enclosing operation sorts first where both start together)
        seq = sorted(ops[p], key=lambda o: (o[0], -o[1]))
        for i, (lo, hi, name, scope) in enumerate(seq):
            if i + 1 < len(seq) and seq[i + 1][0] < hi:
                continue
            mid = (lo + hi) / 2
            r = bisect.bisect_right(starts, mid) - 1
            if r < 0 or mid > runs[r][1]:
                continue
            out[(name, scope)] += (hi - lo) / 1e9
    return dict(out)


def scoped_time(events: List[SpanEvent], program: str = REFINE_PROGRAM,
                ) -> Dict[Optional[str], float]:
    """``refine_ops`` summed by scope."""
    out: Dict[Optional[str], float] = defaultdict(float)
    for (_, scope), t in refine_ops(events, program).items():
        out[scope] += t
    return dict(out)


def sample_step_share(events: List[SpanEvent],
                      program: str = REFINE_PROGRAM) -> Optional[float]:
    """Share (%) of the leaf operation time of the window's ``program``
    executions spent under the ``sample_step`` scope. None where no
    execution is traced or no operation's scope is known."""
    by = scoped_time(events, program)
    total = sum(by.values())
    if total <= 0 or not set(by) - {None}:
        return None
    return 100.0 * by.get("sample_step", 0.0) / total


def dispatch_containment(events: List[SpanEvent],
                         program: str = REFINE_PROGRAM) -> dict:
    """How the window's ``program`` executions sit against the host's
    ``serve.dispatch#k`` spans, on the profiler's one clock: wholly
    inside one (``inside``), overlapping one without being inside it
    (``crossing``, with the most any sticks out, ``most_out_ms``), or
    overlapping none (``outside``)."""
    w_lo, w_hi = _window(events)
    dev = _device(events)
    spans = [(lo, hi) for lo, hi, n in _serve_spans(events)
             if stage(n) == DISPATCH]
    out = {"inside": 0, "crossing": 0, "outside": 0, "most_out_ms": 0.0}
    for p, l, n, s, d, _ in events:
        if p != dev or l != trace_reduce.MODULES_LINE or \
                trace_reduce.program_name(n) != program or \
                s < w_lo or s + d > w_hi:
            continue
        lo, hi = s, s + d
        over = [(a, b) for a, b in spans if a < hi and b > lo]
        if any(a <= lo and hi <= b for a, b in over):
            out["inside"] += 1
        elif over:
            out["crossing"] += 1
            stick = min(max(a - lo, 0.0) + max(hi - b, 0.0) for a, b in over)
            out["most_out_ms"] = max(out["most_out_ms"], stick / 1e6)
        else:
            out["outside"] += 1
    return out


def rescope(events: List[SpanEvent], shape_of: Dict[int, tuple],
            scopes: Dict[tuple, Dict[str, Optional[str]]],
            program: str = REFINE_PROGRAM) -> List[SpanEvent]:
    """Operation names repeat across the compiled programs of different
    shapes with different scopes. Each ``program`` execution that lies
    inside a ``serve.dispatch#k`` span ran micro-batch ``k``'s program:
    scope its operations by that program's text (``shape_of``: k ->
    shape, ``scopes``: shape -> op name -> scope)."""
    dev = _device(events)
    spans = [(lo, hi, int(n.split("#")[1])) for lo, hi, n
             in _serve_spans(events) if stage(n) == DISPATCH and "#" in n]
    runs = []
    for p, l, n, s, d, _ in events:
        if p == dev and l == trace_reduce.MODULES_LINE and \
                trace_reduce.program_name(n) == program:
            k = next((k for a, b, k in spans if a <= s and s + d <= b), None)
            if scopes.get(shape_of.get(k)) is not None:
                runs.append((s, s + d, scopes[shape_of[k]]))
    runs.sort(key=lambda r: r[:2])
    starts = [r[0] for r in runs]
    out = []
    for e in events:
        p, l, n, s, d, _ = e
        if p == dev and l == trace_reduce.OPS_LINE:
            i = bisect.bisect_right(starts, s + d / 2) - 1
            if i >= 0 and s + d / 2 <= runs[i][1]:
                e = (p, l, n, s, d, runs[i][2].get(trace_reduce.op_name(n)))
        out.append(e)
    return out


def idle_gaps(events: List[SpanEvent], top: int = 10, idle=None) -> list:
    """The longest idle stretches of the first device, each named by the
    narrowest host span (program or benchmark, without its ``#k``) that
    covers a fifth of it or more: ``[[name, seconds]]``."""
    _, gaps = idle or _idle(events)
    host = [(s, s + d, n) for p, _, n, s, d, _ in events
            if not trace_reduce.DEVICE_PLANE.match(p)
            and n != trace_reduce.WINDOW_SPAN]
    out = []
    for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        cover = [(e - s, n) for s, e, n in host
                 if min(e, hi) - max(s, lo) >= 0.2 * (hi - lo)]
        name = stage(min(cover)[1]) if cover else "no host span"
        out.append([name, (hi - lo) / 1e9])
    return out
