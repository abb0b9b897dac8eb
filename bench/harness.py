"""What every cell of the benchmark shares: finding a cell's files by name,
the device check, the compile counter, the error arithmetic, the peaks
table and the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json`` (which names the driver under
``bench/drivers/`` that generates it), and each per-layer metric a reader
``bench/metrics/<metric>.py``.  Adding a cell or a metric adds files and
entries; no file here has to change.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


class BenchError(RuntimeError):
    """A run that cannot produce a result: no chip, a missing file."""


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by its path (metric and program files are named after
    their entry in ``BENCHMARK.json``, so they need not be identifiers)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.relative_to(BENCH)))
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


def benchmark_spec(root: Path = ROOT) -> Dict[str, Any]:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json in {root}")
    return load_json(path)


def cell(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    """The workload entry, its configuration and its traffic mix."""
    for w in spec["workloads"]:
        if w["name"] == name:
            break
    else:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    return {"workload": w,
            "config": load_json(ROOT / conf["file"]),
            "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json")}


def end_to_end_of(spec: Dict[str, Any], workload: str) -> List[Dict]:
    """The cell's end-to-end metrics: those that list it under
    ``workloads``, and those with no such key."""
    return [m for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])]


def per_layer_of(spec: Dict[str, Any], workload: str) -> List[Dict]:
    """The cell's per-layer metrics: each names its cells under
    ``workloads``."""
    for m in spec["per_layer"]:
        if "workloads" not in m:
            raise BenchError(f"per-layer metric {m['name']!r} lists no "
                             "workloads in BENCHMARK.json")
    return [m for m in spec["per_layer"] if workload in m["workloads"]]


# ---------------------------------------------------------------------------
# seeds
# ---------------------------------------------------------------------------

def rng_for(seed: int, *stream: int):
    """A numpy Generator for ``seed`` (any whole number, also past 32 bits)
    and a sub-stream: the same (seed, stream) gives the same numbers."""
    import numpy as np
    s = int(seed)
    words = [s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF, int(s < 0)]
    return np.random.default_rng(words + [int(x) for x in stream])


def jax_seed(seed: int, *stream: int) -> int:
    """A 31-bit seed for ``jax.random.key`` drawn from ``seed``."""
    return int(rng_for(seed, *stream).integers(0, 2**31 - 1))


# ---------------------------------------------------------------------------
# device, peaks, compile cache
# ---------------------------------------------------------------------------

def require_chips(chips: int):
    """The devices of this process, or ``BenchError`` when JAX finds no TPU
    or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, found "
                         f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    table = load_json(BENCH / "peaks.json")["kinds"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         "bench/peaks.json")
    return table[device_kind]


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (``JAX_COMPILATION_CACHE_DIR`` when that is set), keeping
    every compile however short."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_record(devices) -> Dict[str, Any]:
    """The devices as JAX reports them, with the peak memory in use on the
    fullest one."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devices)}


def span(name: str, on: bool):
    """A host span in the profiler's trace (``jax.profiler.TraceAnnotation``)
    when ``on``, else nothing."""
    import contextlib
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def context(cell_: Dict[str, Any], *, seed: int, seconds: float, limits,
            devices, trace_dir=None, tracer=None, keep: bool = False):
    """What a driver's ``run`` reads: the cell's files, the run's
    arguments, and how to trace and to read the devices."""
    import contextlib
    return {"config": cell_["config"], "traffic": cell_["traffic"],
            "workload": cell_["workload"], "seed": seed, "seconds": seconds,
            "limits": limits, "window_span": "bench.window",
            "trace_dir": trace_dir, "tracer": tracer or contextlib.nullcontext,
            "device_record": lambda: device_record(devices), "keep": keep}


class CompileClock:
    """Seconds JAX spent compiling (or reading a compiled program back
    from the persistent cache), the compiles and the cache hits, while it
    is open."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == self._EVENT:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)


# ---------------------------------------------------------------------------
# error arithmetic
# ---------------------------------------------------------------------------

def err(out, ref) -> Dict[str, float]:
    import numpy as np
    a = np.asarray(out, np.float64)
    b = np.asarray(ref, np.float64)
    diff = np.abs(a - b)
    if not np.isfinite(a).all():
        return {"max_abs_err": math.inf, "max_rel_err": math.inf,
                "l2_rel_err": math.inf}
    return {"max_abs_err": float(diff.max()),
            "max_rel_err": float(diff.max() / max(np.abs(b).max(), 1e-30)),
            "l2_rel_err": float(np.linalg.norm(diff)
                                / max(np.linalg.norm(b), 1e-30))}


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

def checks_ok(checks: List[Dict[str, Any]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks)


def emit(*, checks: List[Dict[str, Any]], attempted: int, failed: int,
         metrics: Dict[str, Any], device: Dict[str, Any],
         breakdown: Optional[Dict[str, Any]] = None,
         out=None, err_out=None) -> bool:
    """Print each compared number beside its limit as the last lines of
    standard error, and the result as the last line of standard output;
    return ``correct``."""
    out = out or sys.stdout
    err_out = err_out or sys.stderr
    correct = bool(failed == 0 and checks_ok(checks))
    compared = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                for c in checks}
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err_out)
    print(f"check correct: {correct}", file=err_out, flush=True)
    line = {"correct": correct, "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    print(json.dumps(line, default=float), file=out, flush=True)
    return correct
