"""Reduction of a profiler trace to device busy time, op and program
times, and idle gaps named by the benchmark's host spans.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes
are those named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per device operation (a Pallas kernel is one op, named after
its kernel) and the ``XLA Modules`` line one event per run of a jitted
program (``jit_<function>(<id>)``).  The host plane's threads carry the
benchmark's ``TraceAnnotation`` spans, on the same clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# ops whose events enclose other ops' (a scan's while loop): counted in
# busy time through their children, never as ops of their own
_CONTAINER = re.compile(r"^%\S+ = .*?\s(while|conditional|call)\(")

Interval = Tuple[float, float]


@dataclass
class Trace:
    ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)          # device -> [(op, start_s, end_s)]
    modules: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)          # device -> [(program, start, end)]
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def start(trace_dir: Path) -> None:
    """Start the profiler without its Python function tracer: the
    benchmark's spans and the device are what the reduction reads."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path: Path) -> Trace:
    """Device ops, device programs and host spans of one trace file,
    in seconds on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dst = tr.ops.setdefault(plane.name, [])
                elif line.name == "XLA Modules":
                    dst = tr.modules.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dst.append((e.name, s, s + e.duration_ns * 1e-9))
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        tr.spans.append((e.name, s,
                                         s + e.duration_ns * 1e-9))
    return tr


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def window_of(tr: Trace) -> Interval:
    spans = [(s, e) for n, s, e in tr.spans if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return spans[0]


def span_at(tr: Trace, t: float) -> str:
    """The innermost benchmark span (other than the window) around t."""
    best: Optional[Tuple[float, str]] = None
    for n, s, e in tr.spans:
        if n != WINDOW_SPAN and s <= t <= e:
            if best is None or e - s < best[0]:
                best = (e - s, n)
    return best[1] if best else "no span"


@dataclass
class Reduced:
    window_s: float
    busy_s: float                      # mean over devices
    op_seconds: Dict[str, float]       # leaf ops by full text, all devices
    program_seconds: Dict[str, float]
    program_runs: Dict[str, int]
    gaps: List[Tuple[str, float]]      # longest idle gaps, device 0


def program_name(module: str) -> str:
    """``jit_decode_step_paged(123)`` -> ``decode_step_paged``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def reduce(tr: Trace, top: int = 10) -> Reduced:
    lo, hi = window_of(tr)
    devices = sorted(tr.ops)
    if not devices:
        raise ValueError("trace holds no device ops")
    busy, op_s, prog_s, prog_n = [], {}, {}, {}
    for dev in devices:
        busy.append(sum(e - s for s, e in union(
            [(s, e) for _, s, e in tr.ops[dev]], lo, hi)))
        for name, s, e in tr.ops[dev]:
            d = min(e, hi) - max(s, lo)
            if d > 0 and not _CONTAINER.search(name):
                op_s[name] = op_s.get(name, 0.0) + d
        for name, s, e in tr.modules.get(dev, []):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                p = program_name(name)
                prog_s[p] = prog_s.get(p, 0.0) + d
                prog_n[p] = prog_n.get(p, 0) + 1
    merged = union([(s, e) for _, s, e in tr.ops[devices[0]]], lo, hi)
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
             if edges[i + 1] > edges[i]]
    holes.sort(key=lambda h: h[0] - h[1])
    gaps = [(span_at(tr, (s + e) / 2), e - s) for s, e in holes[:top]]
    return Reduced(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                   op_seconds=op_s, program_seconds=prog_s,
                   program_runs=prog_n, gaps=gaps)


def op_label(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...), kind=kLoop`` ->
    ``%fusion.3 fusion``: the op's name and opcode, without operands."""
    if " = " not in name:
        return name[:120]
    lhs, rhs = name.split(" = ", 1)
    m = re.search(r"\s([a-z][\w\-]*)\(", " " + rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def top_ops(red: Reduced, top: int = 10) -> List[List]:
    """The device ops that took most time, labelled by name and opcode."""
    by: Dict[str, float] = {}
    for n, s in red.op_seconds.items():
        by[op_label(n)] = by.get(op_label(n), 0.0) + s
    items = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[n, s] for n, s in items]
