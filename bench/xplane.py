"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Device planes are those named ``/device:TPU:<n>``.  On each, the line of
XLA operations gives the intervals in which the device ran something;
their union is the busy time.  The line of XLA modules gives one event
per program execution, which is the device time of that program.  Host
spans are the benchmark's own ``TraceAnnotation`` events whose names
start with ``bench.``: the window span bounds the traced window, and the
others label the device's idle gaps by what the host was doing.

Everything here reads only ``jax.profiler.ProfileData`` and plain Python,
so the reduction is checked offline on a recorded trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")   # not its SparseCore planes
SPAN_PREFIX = "bench."

_ID_IN_NAME = re.compile(r"^(?P<name>.*?)\((?P<id>-?\d+)\)$")


@dataclasses.dataclass
class Execution:
    """One program execution on one device."""
    device: str
    module: str
    program_id: Optional[int]
    start_ns: float
    end_ns: float

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class TraceSummary:
    window_s: float                      # length of the traced window
    busy_s: float                        # union of op intervals, mean over devices
    devices: int
    executions: List[Execution]          # module executions inside the window
    op_seconds: Dict[str, float]         # device seconds per op name (summed over devices)
    gaps: List[Tuple[str, float]]        # idle gaps on the first device, labelled

    def executions_of(self, *, module: Optional[str] = None,
                      program_ids=None) -> List[Execution]:
        out = self.executions
        if module is not None:
            out = [e for e in out if e.module == module]
        if program_ids is not None:
            ids = set(program_ids)
            out = [e for e in out if e.program_id in ids]
        return out

    def breakdown(self, n: int = 10) -> Dict[str, List]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:
        return {}


def _module_of(ev) -> Tuple[str, Optional[int]]:
    """(module name, program id) of a module-line event: the id is the
    event's ``program_id`` stat, or the number in ``name(<id>)``."""
    st = _stats(ev)
    name = ev.name
    m = _ID_IN_NAME.match(name)
    pid = st.get("program_id")
    if m:
        name = m.group("name")
        if pid is None:
            pid = int(m.group("id"))
    return name, (int(pid) if pid is not None else None)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _label(spans: List[Tuple[str, float, float]], t: float) -> str:
    """The innermost host span that covers ``t`` (the shortest one), or
    ``idle`` where the host was in none."""
    best = None
    for name, a, b in spans:
        if a <= t <= b and (best is None or b - a < best[2] - best[1]):
            best = (name, a, b)
    return best[0] if best else "idle"


def reduce(path: str, **kw) -> TraceSummary:
    """Reduce the trace at ``path`` (a file, or a directory holding one)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    return summarize(ProfileData.from_file(path), **kw)


def summarize(pd, *, window_span: Optional[str] = None,
              span_prefix: str = SPAN_PREFIX) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``.  The window is the host span
    ``window_span`` (an error where the trace has none), or with ``None``
    the extent of the device's operations."""
    spans: List[Tuple[str, float, float]] = []
    dev_ops: Dict[str, List[Tuple[str, float, float]]] = {}
    dev_mods: Dict[str, list] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend(line.events)
            dev_ops[plane.name] = ops
            dev_mods[plane.name] = [
                (*_module_of(ev), ev.start_ns, ev.start_ns + ev.duration_ns)
                for ev in mods]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(span_prefix):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    if not dev_ops:
        raise ValueError("no /device:TPU:<n> plane in the trace")

    windows = [(a, b) for n, a, b in spans if n == window_span]
    if window_span is not None:
        if not windows:
            raise ValueError(f"no host span {window_span!r} in the trace")
        lo, hi = min(a for a, _ in windows), max(b for _, b in windows)
    else:
        every = [t for ops in dev_ops.values() for _, a, b in ops
                 for t in (a, b)]
        lo, hi = min(every), max(every)
    inner = [s for s in spans if s[0] != window_span]

    busy_total, op_seconds, executions = 0.0, {}, []
    gaps: List[Tuple[str, float]] = []
    for i, dev in enumerate(sorted(dev_ops)):
        ops = [(n, a, b) for n, a, b in dev_ops[dev] if b > lo and a < hi]
        busy = _clip(union([(a, b) for _, a, b in ops]), lo, hi)
        busy_total += sum(b - a for a, b in busy)
        for n, a, b in ops:
            op_seconds[n] = op_seconds.get(n, 0.0) + (
                min(b, hi) - max(a, lo)) * 1e-9
        executions.extend(Execution(dev, m, pid, a, b)
                          for m, pid, a, b in dev_mods[dev]
                          if a >= lo and a < hi)
        if i == 0:
            edges = [lo] + [t for iv in busy for t in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((_label(inner, (a + b) / 2), (b - a) * 1e-9))
    executions.sort(key=lambda e: e.start_ns)
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=busy_total * 1e-9 / len(dev_ops),
                        devices=len(dev_ops), executions=executions,
                        op_seconds=op_seconds, gaps=gaps)

