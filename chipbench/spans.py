#!/usr/bin/env python3
"""The serving program's own host spans in a profiler trace: where each
decode step's host time goes, and what the host was doing in each of the
device's idle gaps.

The program marks the parts of its serving step with
``jax.profiler.TraceAnnotation`` spans named ``repro.<layer>.<part>``,
with counts as the spans' arguments (``docs/tracing.md``); the
benchmark's own spans are ``chipbench.*``.  Both land on the host plane
of the ``.xplane.pb``, one line per host thread, on the device trace's
clock.  The serving thread is the line that holds ``chipbench.window``.

    python3 chipbench/spans.py <trace dir or .xplane.pb>

prints, as one JSON object, the split of the window's decode steps
(:func:`split`), the host time and the device idle time per step by the
innermost span on the serving thread, and the longest device idle gaps
named by that span.
"""
from __future__ import annotations

import functools
import json
import sys
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))

from chipbench import trace  # noqa: E402

PREFIX = "repro."
STEP = "repro.engine.step"
DISPATCH = "repro.engine.decode_dispatch"
WAITS = ("repro.engine.decode_wait", "repro.engine.prefill_wait")
SHELL = ("repro.port.", "repro.mmu.", "repro.health.")
THREADS = "repro.sched."

Interval = Tuple[float, float]


@dataclass
class Span:
    name: str
    line: str                          # host plane and thread line
    start: float                       # seconds, on the trace's clock
    end: float
    args: Dict[str, object] = field(default_factory=dict)


def read(path: Path) -> List[Span]:
    """Every ``repro.*`` and ``chipbench.*`` span of the host planes,
    with its thread line and its arguments."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith((PREFIX, trace.SPAN_PREFIX)):
                    s = e.start_ns * 1e-9
                    out.append(Span(e.name, f"{plane.name}#{k}", s,
                                    s + e.duration_ns * 1e-9,
                                    dict(e.stats)))
    return out


def window(spans: List[Span]) -> Optional[Tuple[Interval, str]]:
    """The traced window and the serving thread's line."""
    for s in spans:
        if s.name == trace.WINDOW_SPAN:
            return (s.start, s.end), s.line
    return None


# -------------------------------------------------------------- intervals --
def length(iv: List[Interval]) -> float:
    return sum(e - s for s, e in iv)


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` less ``b``, both merged and sorted."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        cur, k = s, j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ------------------------------------------------------------------ split --
@dataclass
class Split:
    """Per decode step of the window, in ms.  The first three split the
    ``repro.engine.step`` spans: they sum to ``step_ms``."""
    steps: int                         # decode dispatches in the window
    step_ms: float                     # union of repro.engine.step / steps
    device_wait_ms: float              # host blocked on the device
    shell_host_ms: float               # port, MMU, health, outside waits
    engine_host_ms: float              # the rest of the step
    shell_thread_ms: float             # repro.sched.* on other threads


def split(spans: List[Span]) -> Optional[Split]:
    """The window's decode steps split by layer; None where the trace
    holds no window or no ``repro.engine.decode_dispatch`` in it."""
    win = window(spans)
    if win is None:
        return None
    (lo, hi), line = win
    mine = [s for s in spans if s.line == line]
    steps = sum(1 for s in mine if s.name == DISPATCH and lo <= s.start <= hi)
    if not steps:
        return None

    def cover(keep, among) -> List[Interval]:
        return trace.union([(s.start, s.end) for s in among if keep(s.name)],
                           lo, hi)
    step = cover(lambda n: n == STEP, mine)
    wait = intersect(cover(lambda n: n in WAITS, mine), step)
    shell = subtract(intersect(cover(lambda n: n.startswith(SHELL), mine),
                               step), wait)
    threads = cover(lambda n: n.startswith(THREADS),
                    [s for s in spans if s.line != line])
    per = 1e3 / steps
    step_ms = length(step) * per
    wait_ms, shell_ms = length(wait) * per, length(shell) * per
    return Split(steps=steps, step_ms=step_ms, device_wait_ms=wait_ms,
                 shell_host_ms=shell_ms,
                 engine_host_ms=step_ms - wait_ms - shell_ms,
                 shell_thread_ms=length(threads) * per)


@functools.lru_cache(maxsize=2)
def _split_of(path: str, mtime_ns: int) -> Optional[Split]:
    return split(read(Path(path)))


def of_run(run) -> Optional[Split]:
    """The split of a traced chip run, from the trace the harness wrote
    under ``.chipbench_trace/<cell>``; None for a run without a device
    trace, and for a program that records no ``repro.*`` spans.  Read
    once for all the metrics of one run."""
    if run.trace is None:
        return None
    try:
        path = trace.find_xplane(ROOT / ".chipbench_trace" / run.cell)
    except FileNotFoundError:
        return None
    return _split_of(str(path), path.stat().st_mtime_ns)


# ------------------------------------------------------ serving thread --
def serving(spans: List[Span]) -> List[Span]:
    """The serving thread's spans other than the window."""
    win = window(spans)
    if win is None:
        return []
    return [s for s in spans
            if s.line == win[1] and s.name != trace.WINDOW_SPAN]


def innermost(spans: List[Span], t: float) -> str:
    """The shortest of ``spans`` around ``t``."""
    best: Optional[Span] = None
    for s in spans:
        if s.start <= t <= s.end and (
                best is None or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best else "no span"


def segments(spans: List[Span]) -> List[Tuple[str, float, float]]:
    """The window cut where the serving thread's innermost span changes
    (spans of one thread nest): ``(span or "no span", start, end)``."""
    win = window(spans)
    if win is None:
        return []
    (lo, hi), _ = win
    out: List[Tuple[str, float, float]] = []

    def add(name: str, s: float, e: float) -> None:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    stack: List[Span] = []
    cur = lo
    for s in sorted(serving(spans), key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            top = stack.pop()
            add(top.name, cur, top.end)
            cur = max(cur, top.end)
        add(stack[-1].name if stack else "no span", cur, s.start)
        cur = max(cur, s.start)
        stack.append(s)
    while stack:
        top = stack.pop()
        add(top.name, cur, top.end)
        cur = max(cur, top.end)
    add("no span", cur, hi)
    return out


def holes(tr: trace.Trace) -> List[Interval]:
    """The first device's idle intervals in the window, as
    :func:`chipbench.trace.reduce` finds them."""
    lo, hi = trace.window_of(tr)
    merged = trace.union([(s, e) for _, s, e in tr.ops[sorted(tr.ops)[0]]],
                         lo, hi)
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _per_step(spans: List[Span], cut) -> Dict[str, float]:
    sp = split(spans)
    if sp is None:
        return {}
    out: Dict[str, float] = {}
    for name, s, e in cut:
        out[name] = out.get(name, 0.0) + (e - s) * 1e3 / sp.steps
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def self_ms(spans: List[Span]) -> Dict[str, float]:
    """Host time of the window per decode step, by the innermost span on
    the serving thread."""
    return _per_step(spans, segments(spans))


def idle_ms(tr: trace.Trace, spans: List[Span]) -> Dict[str, float]:
    """Device idle time of the window per decode step, by the innermost
    span the serving thread was in meanwhile."""
    cut = segments(spans)
    idle = intersect([(s, e) for _, s, e in cut], holes(tr))
    named, j = [], 0
    for name, s, e in cut:                  # segments tile the window
        while j < len(idle) and idle[j][1] <= e:
            named.append((name, idle[j][0], idle[j][1]))
            j += 1
    return _per_step(spans, named)


def name_gaps(tr: trace.Trace, spans: List[Span],
              top: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps of the first device in the window, each
    named by the innermost span the serving thread was in at its
    middle."""
    longest = sorted(holes(tr), key=lambda h: h[0] - h[1])[:top]
    mine = serving(spans)
    return [(innermost(mine, (s + e) / 2), e - s) for s, e in longest]


def main(argv: List[str]) -> int:
    path = Path(argv[0])
    if path.is_dir():
        path = trace.find_xplane(path)
    spans = read(path)
    sp = split(spans)
    out: Dict[str, object] = {
        "split": asdict(sp) if sp else None,
        "self_ms": self_ms(spans),
        "spans": {n: sum(1 for s in spans if s.name == n)
                  for n in sorted({s.name for s in spans})}}
    tr = trace.read(path)
    if tr.ops:
        out["idle_ms"] = idle_ms(tr, spans)
        out["idle_gaps"] = [list(g) for g in name_gaps(tr, spans)]
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
