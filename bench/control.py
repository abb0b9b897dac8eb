#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed, drive the cell as a run does, for a short window at the
cell's own load, and print one JSON line with the number compared for the
program (``program``) and for the control (``control``): the plain
reference put in the program's place and computed one precision below the
configuration's, on the same inputs.  A cell's limit lies above the
largest program reading and below the smallest control reading.  The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.harness import BENCH, BenchError  # noqa: E402


def readings(spec, name, seeds, seconds, devices):
    import jax
    c = harness.cell(spec, name)
    limits = harness.load_json(BENCH / "limits" / f"{name}.json")
    driver = harness.load_module(
        BENCH / "drivers" / f"{c['traffic']['driver']}.py")
    for seed in seeds:
        result = driver.run(harness.context(
            c, seed=seed, seconds=seconds, limits=limits, devices=devices,
            keep=True))
        check = result["checks"][0]
        rec = {"workload": name, "seed": seed, "check": check["name"],
               "program": check["value"],
               "control": driver.control_reading(result["kept"]),
               "limit": check["limit"], "attempted": result["attempted"],
               "failed": result["failed"]}
        for key in ("served_logit_gap_widest",):
            if key in result["observed"]:
                rec["program_widest"] = result["observed"][key]
                rec["control_widest"] = result["kept"].get("control_widest")
        # nothing of this seed stays on the device while the next runs
        for leaf in jax.tree.leaves(result.pop("kept")):
            if hasattr(leaf, "delete"):
                leaf.delete()
        del result
        yield rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    try:
        spec = harness.benchmark_spec()
        c = harness.cell(spec, args.workload)
        devices = harness.require_chips(int(c["workload"]["chips"]))
    except BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    for rec in readings(spec, args.workload, seeds, args.seconds, devices):
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
