"""Tiny sizes of the benchmark's cells, for its tests on the CPU.

Each cell brings its own tiny sizes in ``bench/tiny_sizes/<cell>.json``:
``{"patch": {...}}``, merged key by key into a copy of the cell's
configuration.  ``spec(tmp)`` is ``BENCHMARK.json`` with each
configuration's file replaced by such a copy written under ``tmp``: the
same cells, traffic mixes, limits and drivers, so a test drives every
step of a run but the chip.
"""
from __future__ import annotations

import copy
import json
from pathlib import Path

from bench import harness

SIZES = harness.BENCH / "tiny_sizes"


def merge(base, patch):
    """``base`` with ``patch`` laid over it, dict by dict."""
    out = dict(base)
    for k, v in patch.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = v
    return out


def _agree(a, b, where=""):
    """Raise where two cells' patches set one key to different values."""
    for k in a.keys() & b.keys():
        if isinstance(a[k], dict) and isinstance(b[k], dict):
            _agree(a[k], b[k], f"{where}{k}.")
        elif a[k] != b[k]:
            raise harness.BenchError(f"tiny sizes disagree on {where}{k}")


def spec(tmp: Path):
    out = copy.deepcopy(harness.benchmark_spec())
    patches = {c["name"]: {} for c in out["configs"]}
    for w in out["workloads"]:
        path = SIZES / f"{w['name']}.json"
        if not path.is_file():
            raise harness.BenchError(f"no tiny sizes for {w['name']}: "
                                     f"add {path}")
        patch = harness.load_json(path)["patch"]
        _agree(patches[w["config"]], patch)
        patches[w["config"]] = merge(patches[w["config"]], patch)
    for c in out["configs"]:
        cfg = merge(harness.load_json(harness.ROOT / c["file"]),
                    patches[c["name"]])
        path = Path(tmp) / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    return out
