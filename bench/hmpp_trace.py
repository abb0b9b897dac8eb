"""What one planner execution spends between upload, device and download,
read from the executor's ``hmpp.*`` host spans in a profiler trace.

The executor opens a host span for each HMPP directive it performs
(``hmpp.execute`` around the call, ``hmpp.advancedload``,
``hmpp.callsite``, ``hmpp.synchronize``, ``hmpp.delegatestore``,
``hmpp.release``).  The profiler records them on the clock of the device
planes, so for each ``hmpp.execute`` span k of the window:

- ``h2d_ms``: the start of the first device module execution that begins
  inside k at or after k's first ``hmpp.callsite``, minus the start of
  k's first ``hmpp.advancedload`` (host staging, DMA and dispatch up to
  the moment the offloaded program starts on the chip);
- ``d2h_ms``: the end of k's last ``hmpp.delegatestore`` minus the end of
  the last device module execution that begins inside k (from the chip
  finishing to the output being on the host);

each the mean over k.  ``clock_pct`` is the share of the window's module
executions that start inside an ``hmpp.execute`` span after its first
``hmpp.callsite``: near 100 only where host and device share one clock.

    python -m bench.hmpp_trace read <trace dir or .xplane.pb>
    python -m bench.hmpp_trace record <dir> [--n 256] [--executions 3]

``read`` prints these numbers and the window's idle gaps, labelled by the
innermost ``bench.*`` or ``hmpp.*`` span, as one JSON object.  ``record``
traces a small 3mm the way the planner cell runs it (on the chip).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
from typing import Dict, List, Optional, Tuple

from bench import xplane

SPANS = ("bench.", "hmpp.")
Span = Tuple[str, float, float]


def host_spans(pd, prefixes=SPANS) -> List[Span]:
    """(name, start_ns, end_ns) of the host events named with a prefix,
    by start."""
    out = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
           for plane in pd.planes if plane.name.startswith("/host")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(prefixes)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def per_execution(spans: List[Span], executions) -> List[Dict[str, float]]:
    """For each ``hmpp.execute`` span that holds a callsite and device
    work: its length, h2d, d2h and module time, in ns."""
    out = []
    for _, a, b in (s for s in spans if s[0] == "hmpp.execute"):
        inside = [s for s in spans if a <= s[1] and s[2] <= b]

        def named(n):
            return [s for s in inside if s[0] == n]
        loads, calls = named("hmpp.advancedload"), named("hmpp.callsite")
        stores = named("hmpp.delegatestore")
        ex = [e for e in executions if a <= e.start_ns < b]
        if not calls or not ex:
            continue
        started = [e.start_ns for e in ex if e.start_ns >= calls[0][1]]
        out.append({
            "execute": b - a,
            "device": sum(e.end_ns - e.start_ns for e in ex),
            "h2d": started[0] - loads[0][1] if loads and started else None,
            "d2h": (stores[-1][2] - max(e.end_ns for e in ex)
                    if stores else None)})
    return out


def _mean_ms(rows, key) -> Optional[float]:
    vals = [r[key] for r in rows if r[key] is not None]
    return statistics.fmean(vals) * 1e-6 if vals else None


def clock_pct(spans: List[Span], executions) -> Optional[float]:
    """Share of ``executions`` that start inside an ``hmpp.execute`` span
    at or after its first ``hmpp.callsite``."""
    starts = []
    for _, a, b in (s for s in spans if s[0] == "hmpp.execute"):
        calls = [s[1] for s in spans
                 if s[0] == "hmpp.callsite" and a <= s[1] <= b]
        if calls:
            starts.append((calls[0], b))
    if not executions:
        return None
    hit = sum(any(c <= e.start_ns < b for c, b in starts)
              for e in executions)
    return 100.0 * hit / len(executions)


def read(pd, window_span: Optional[str] = "bench.window") -> Dict:
    """Every number of the module docstring, from a ``ProfileData``."""
    summary = xplane.summarize(pd, window_span=window_span,
                               span_prefix=SPANS)
    spans = host_spans(pd)
    if window_span is not None:
        lo = min(a for n, a, _ in spans if n == window_span)
        hi = max(b for n, _, b in spans if n == window_span)
        spans = [s for s in spans if lo <= s[1] < hi]
    rows = per_execution(spans, summary.executions)
    return {"executions": len(rows),
            "h2d_ms": _mean_ms(rows, "h2d"),
            "device_ms": _mean_ms(rows, "device"),
            "d2h_ms": _mean_ms(rows, "d2h"),
            "execute_ms": _mean_ms(rows, "execute"),
            "clock_pct": clock_pct(spans, summary.executions),
            "window_s": summary.window_s, "busy_s": summary.busy_s,
            "idle_gaps": summary.breakdown()["idle_gaps"]}


def record(log_dir: str, n: int = 256, executions: int = 3,
           seed: int = 0) -> None:
    """Trace ``executions`` back-to-back executions of 3mm at every size
    ``n``, warmed first, as the planner cell's driver runs them."""
    import jax

    from bench import harness
    from bench import run as bench_run
    from repro.core import execute, plan

    program = harness.load_module(harness.BENCH / "programs" / "3mm.py")
    ds = dict.fromkeys(("NI", "NJ", "NK", "NL", "NM"), n)
    inputs = program.make_inputs(ds, harness.rng_for(seed, 1, 0))
    pl = plan(program.build(ds, inputs))
    kw = dict(mode="compiled", backend="jax")
    with jax.default_matmul_precision("highest"):
        execute(pl, inputs, **kw)
        with bench_run.profiler(log_dir)():
            with harness.span("bench.window", True):
                for _ in range(executions):
                    with harness.span("bench.execute", True):
                        execute(pl, inputs, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("read")
    r.add_argument("trace")
    r.add_argument("--window-span", default="bench.window")
    w = sub.add_parser("record")
    w.add_argument("log_dir")
    w.add_argument("--n", type=int, default=256)
    w.add_argument("--executions", type=int, default=3)
    args = ap.parse_args(argv)
    if args.cmd == "record":
        record(args.log_dir, n=args.n, executions=args.executions)
        print(xplane.find_xplane(args.log_dir))
        return 0
    from jax.profiler import ProfileData
    path = args.trace
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    print(json.dumps(read(ProfileData.from_file(path),
                          window_span=args.window_span or None)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
