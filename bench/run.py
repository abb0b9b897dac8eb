#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is the entry ``<name>`` of ``workloads`` in ``BENCHMARK.json``.
Its traffic file names the driver that loads the program, warms every
shape the cell uses (set-up), measures for ``--seconds`` seconds and then
checks what the window produced against the plain reference.  With
``--trace 0`` the result line holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line holds the
cell's per-layer metrics, each read from the run by its own file under
``bench/metrics/``.

The last line of standard output is the result, one JSON object; the last
lines of standard error are the numbers compared, each beside its limit.
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)             # bench/'s files are modules of the package
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.harness import BENCH, BenchError  # noqa: E402


def profiler(log_dir):
    """A context manager that records the profiler's trace to ``log_dir``
    (host spans of the benchmark and device activity, no Python tracer)."""
    @contextlib.contextmanager
    def tracer():
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    return tracer


def per_layer(spec, name, result, trace_summary, peak, chips):
    """Each of the cell's per-layer metrics that its reader finds."""
    obs = dict(result["observed"], trace=trace_summary, peak=peak,
               chips=chips)
    out = {}
    for m in harness.per_layer_of(spec, name):
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(spec, name, *, seed, seconds, trace, devices, trace_dir=None,
             out=None, err_out=None) -> bool:
    """Drive the cell ``name`` once and print its result; return
    ``correct``."""
    c = harness.cell(spec, name)
    limits = harness.load_json(BENCH / "limits" / f"{name}.json")
    driver = harness.load_module(
        BENCH / "drivers" / f"{c['traffic']['driver']}.py")
    own_dir = trace and trace_dir is None
    if own_dir:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        ctx = harness.context(
            c, seed=seed, seconds=seconds, limits=limits, devices=devices,
            trace_dir=trace_dir if trace else None,
            tracer=(profiler(os.path.join(trace_dir, "window"))
                    if trace else None))
        result = driver.run(ctx)
        device = result["device"]
        breakdown = None
        if trace:
            from bench import xplane
            # on the chip every listed metric has to be read; a CPU run
            # (the rehearsal) has no device trace and reads none of them
            on_chip = devices[0].platform == "tpu"
            try:
                summary = xplane.reduce(os.path.join(trace_dir, "window"),
                                        window_span=ctx["window_span"])
            except (ValueError, FileNotFoundError) as e:
                if on_chip:
                    raise BenchError(f"the window's trace: {e}") from e
                summary = None
            peak = harness.peaks(devices[0].device_kind) if on_chip else None
            metrics = per_layer(spec, name, result, summary, peak,
                                len(devices))
            missing = [m["name"] for m in harness.per_layer_of(spec, name)
                       if m["name"] not in metrics]
            if on_chip and missing:
                raise BenchError(f"no reading for {', '.join(missing)}")
            if summary is not None:
                device = dict(device, busy_s=summary.busy_s,
                              window_s=summary.window_s)
                breakdown = summary.breakdown()
        else:
            units = {m["name"]: m["unit"]
                     for m in harness.end_to_end_of(spec, name)}
            metrics = {k: {"value": float(result["end_to_end"][k]),
                           "unit": u} for k, u in units.items()}
    finally:
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return harness.emit(checks=result["checks"],
                        attempted=result["attempted"],
                        failed=result["failed"], metrics=metrics,
                        device=device, breakdown=breakdown, out=out,
                        err_out=err_out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace in this directory")
    args = ap.parse_args(argv)
    try:
        spec = harness.benchmark_spec()
        c = harness.cell(spec, args.workload)
        if not (harness.SRC / "repro").is_dir():
            raise BenchError("no program: src/repro is missing")
        devices = harness.require_chips(int(c["workload"]["chips"]))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    try:
        run_cell(spec, args.workload, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), devices=devices,
                 trace_dir=args.trace_dir)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
