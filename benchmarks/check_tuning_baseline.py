"""Tuning-regression gate (ISSUE 5 satellite).

CI runs this after the ``--tune --quick`` smoke step.  It guards the
plan-space tuner's DETERMINISTIC surface — the cost model's predicted
ranking — against silent regressions:

1. Re-enumerates the gate programs (the ``directive_micro`` benchmark
   programs + the 3mm worked example + the flash-attention step with its
   kernel tile axis, at ``--quick`` sizes) with ``measure=False``,
   default hardware constants, and no cache, and compares the predicted
   winner label + predicted cost + valid-candidate count + enumerated
   kernel-variant count against ``tests/golden/tuning_baseline.json``.
2. Cross-checks ``tuning_report.json`` (the artifact the smoke step just
   wrote, ``--report PATH``): its predicted-rank-1 candidate per program
   must match the golden winner within the same tolerance.  The measured
   winner is reported but NOT gated — wall-clock noise on shared CI
   runners picks among near-equal candidates, whereas the predicted
   ordering is reproducible.

Exit status 1 on any regression.  Regenerate after an intentional
cost-model change (bump ``COST_MODEL_VERSION`` too) with:

    PYTHONPATH=src python benchmarks/check_tuning_baseline.py --update

``--update`` also regenerates ``tests/golden/calibration_3mm.json``, the
calibration round-trip fixture: real predicted-term rows from the gate
programs with measured times synthesized from ground-truth constants
that disagree with the defaults (so the default ranking is provably
imperfect and a correct least-squares fit provably repairs it).
"""
from __future__ import annotations

import json
import pathlib
import sys
from typing import Dict, List

GOLDEN_DIR = pathlib.Path(__file__).resolve().parent.parent / "tests" / "golden"
BASELINE_PATH = GOLDEN_DIR / "tuning_baseline.json"
CALIBRATION_PATH = GOLDEN_DIR / "calibration_3mm.json"

# the baseline is defined at the CI smoke sizes (directive_micro --quick)
QUICK_N, QUICK_ITERS = 256, 4

REL_TOL = 0.05   # predicted_s drift allowed (HLO flop counts move a
                 # little across jax versions; label changes never do)

# ground truth for the synthesized calibration fixture: slow link, fat
# per-dispatch overheads, and a device roofline (hbm_bw /
# peak_flops_bf16, machine balance 10 flop/byte) far from HW defaults —
# so dispatch-heavy candidates reorder vs. the default prediction AND
# the fixture has both compute-bound and memory-bound rows for the
# joint two-level fit to separate
_CAL_TRUE = {"pcie_bw": 4e9, "launch_overhead_s": 8e-4,
             "sync_overhead_s": 2e-4,
             "hbm_bw": 2e11, "peak_flops_bf16": 2e12}
_CAL_ROW_KEYS = ("label", "h2d_bytes", "d2h_bytes", "loads", "stores",
                 "syncs", "dispatches", "flops", "kernel_bytes",
                 "kernel_s", "predicted_s")


def _gate_programs() -> Dict[str, object]:
    import directive_micro as dm
    import jax
    from repro.optim.offload import attention_step_program
    from repro.polybench import build_3mm
    saved = dm.N, dm.ITERS
    dm.N, dm.ITERS = QUICK_N, QUICK_ITERS
    try:
        progs = {
            "fig4_advancedload": dm._advancedload_prog(),
            "fig5_delegatestore": dm._delegatestore_prog(),
            "table2_3mm": build_3mm(n=QUICK_N)[0],
            "attn_step": attention_step_program(
                n_steps=1, interpret=jax.default_backend() == "cpu"),
        }
    finally:
        dm.N, dm.ITERS = saved
    return progs


def _predicted_rank1(candidates: List[Dict]) -> Dict:
    return next(c for c in candidates if c["valid"] and c["rank"] == 1)


def compute_baseline() -> Dict[str, Dict]:
    """Deterministic per-program baseline: predicted winner under
    default constants, no measurement, no cache, no calibration."""
    from directive_micro import n_kernel_variants
    from repro.core import tune
    from repro.core.verify import verify_plan
    out = {}
    for name, prog in sorted(_gate_programs().items()):
        pl = tune(prog, backend="numpy", measure=False, cache=False,
                  use_calibration=False)
        valid = [c for c in pl.meta["tuning"]["candidates"] if c["valid"]]
        top = _predicted_rank1(valid)
        tuning = pl.meta["tuning"]
        out[name] = {
            "predicted_winner": top["label"],
            "predicted_s": top["predicted_s"],
            "n_valid": len(valid),
            "n_kernel_variants": n_kernel_variants(valid),
            # the multi-objective surface (ISSUE 10) is as deterministic
            # as the predicted ranking: the rank-1 candidate's modeled
            # joules and residency-walk peak, the per-objective winner
            # labels, and the Pareto point count are all gated
            "energy_j": top["energy_j"],
            "peak_bytes": top["peak_bytes"],
            "winners": dict(tuning["winners"]),
            "n_pareto": len(tuning["pareto"]),
            # the winning plan must pass the static verifier
            # (repro.core.verify) — a cost-model change that promotes
            # a racy/inconsistent candidate is a regression even if
            # its predicted cost looks great
            "verified": bool(verify_plan(pl).ok),
        }
    return out


def _build_calibration_rows() -> Dict:
    from repro.core import tune
    from repro.polybench import build
    from repro.roofline.analysis import HW, offload_cost_terms
    progs = dict(_gate_programs())
    progs["gemm"] = build("gemm", n=QUICK_N, iters=8)[0]
    progs["jacobi2d"] = build("jacobi2d", n=QUICK_N, iters=8)[0]
    hw_true = dict(HW)
    hw_true.update(_CAL_TRUE)
    rows = []
    for name, prog in sorted(progs.items()):
        pl = tune(prog, backend="numpy", measure=False, cache=False,
                  use_calibration=False)
        for c in pl.meta["tuning"]["candidates"]:
            if c["valid"] and c["alias_of"] is None:
                row = {k: c[k] for k in _CAL_ROW_KEYS}
                row["program"] = name
                row["measured_s"] = offload_cost_terms(
                    c["h2d_bytes"], c["d2h_bytes"], c["dispatches"],
                    c["syncs"], c["flops"], c["kernel_bytes"],
                    hw=hw_true)["predicted_s"]
                rows.append(row)
    return {"true_hw": _CAL_TRUE, "rows": rows,
            "note": "measured_s synthesized from true_hw via "
                    "offload_cost_terms over real predicted terms; "
                    "regenerate: PYTHONPATH=src python "
                    "benchmarks/check_tuning_baseline.py --update"}


def update() -> None:
    from repro.core import COST_MODEL_VERSION
    baseline = {
        "cost_model_version": COST_MODEL_VERSION,
        "params": {"N": QUICK_N, "ITERS": QUICK_ITERS},
        "rel_tol": REL_TOL,
        "programs": compute_baseline(),
    }
    GOLDEN_DIR.mkdir(parents=True, exist_ok=True)
    BASELINE_PATH.write_text(json.dumps(baseline, indent=2, sort_keys=True)
                             + "\n")
    CALIBRATION_PATH.write_text(
        json.dumps(_build_calibration_rows(), indent=2, sort_keys=True)
        + "\n")
    print(f"wrote {BASELINE_PATH}\nwrote {CALIBRATION_PATH}")


def check(report_path: str = None) -> List[str]:
    """Compare current predictions (and optionally a tuning_report.json)
    against the golden baseline; returns a list of regression messages
    (empty = pass)."""
    from repro.core import COST_MODEL_VERSION
    golden = json.loads(BASELINE_PATH.read_text())
    tol = golden.get("rel_tol", REL_TOL)
    problems = []
    if golden["cost_model_version"] != COST_MODEL_VERSION:
        problems.append(
            f"cost-model version drift: golden v{golden['cost_model_version']}"
            f" vs current v{COST_MODEL_VERSION} — regenerate the baseline "
            "(--update) alongside the version bump")
    current = compute_baseline()
    for name, want in sorted(golden["programs"].items()):
        got = current.get(name)
        if got is None:
            problems.append(f"{name}: gate program disappeared")
            continue
        if got["predicted_winner"] != want["predicted_winner"]:
            problems.append(
                f"{name}: predicted winner changed "
                f"{want['predicted_winner']} -> {got['predicted_winner']}")
        drift = abs(got["predicted_s"] - want["predicted_s"]) \
            / max(want["predicted_s"], 1e-30)
        if drift > tol:
            problems.append(
                f"{name}: predicted cost drifted {drift:.1%} "
                f"({want['predicted_s']:.3e}s -> {got['predicted_s']:.3e}s, "
                f"tol {tol:.0%})")
        if got["n_valid"] < want["n_valid"]:
            problems.append(
                f"{name}: valid candidates shrank "
                f"{want['n_valid']} -> {got['n_valid']}")
        if got["n_kernel_variants"] < want.get("n_kernel_variants", 1):
            problems.append(
                f"{name}: enumerated kernel variants shrank "
                f"{want['n_kernel_variants']} -> "
                f"{got['n_kernel_variants']} — the kernel tile axis "
                "stopped being explored")
        for col in ("energy_j", "peak_bytes"):
            if col not in want:
                continue          # pre-multi-objective golden
            drift = abs(got[col] - want[col]) / max(want[col], 1e-30)
            if drift > tol:
                problems.append(
                    f"{name}: {col} drifted {drift:.1%} "
                    f"({want[col]:.3e} -> {got[col]:.3e}, tol {tol:.0%})")
        for obj, label in sorted(want.get("winners", {}).items()):
            if got["winners"].get(obj) != label:
                problems.append(
                    f"{name}: {obj}-objective winner changed "
                    f"{label} -> {got['winners'].get(obj)}")
        if got.get("n_pareto", 0) < want.get("n_pareto", 0):
            problems.append(
                f"{name}: Pareto frontier shrank "
                f"{want['n_pareto']} -> {got['n_pareto']} points")
        if not got["verified"]:
            problems.append(
                f"{name}: tuned winner {got['predicted_winner']} no "
                "longer passes the static plan verifier "
                "(races / transfer consistency / donation safety)")
    if report_path:
        problems += _check_report(report_path, golden, tol)
    return problems


def _check_report(report_path: str, golden: Dict, tol: float) -> List[str]:
    """The CI artifact's predicted-rank-1 row must agree with the golden
    baseline (the report is produced with default pricing —
    ``bench_tuner`` passes ``use_calibration=False`` for exactly this)."""
    try:
        report = json.loads(pathlib.Path(report_path).read_text())
    except (OSError, ValueError) as e:
        return [f"tuning report {report_path} unreadable: {e}"]
    problems = []
    for name, want in sorted(golden["programs"].items()):
        tuning = report.get("programs", {}).get(name)
        if tuning is None:
            problems.append(f"{name}: missing from {report_path}")
            continue
        top = _predicted_rank1(tuning["candidates"])
        if top["label"] != want["predicted_winner"]:
            problems.append(
                f"{name}: report predicted winner {top['label']} != "
                f"golden {want['predicted_winner']}")
        drift = abs(top["predicted_s"] - want["predicted_s"]) \
            / max(want["predicted_s"], 1e-30)
        if drift > tol:
            problems.append(
                f"{name}: report predicted cost drifted {drift:.1%} "
                f"from golden (tol {tol:.0%})")
        chosen = next(c for c in tuning["candidates"]
                      if c["label"] == tuning["chosen"])
        if not chosen.get("measured_s"):
            problems.append(f"{name}: report winner was never measured")
    return problems


def main(argv=None) -> int:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    args = list(sys.argv[1:] if argv is None else argv)
    if "--update" in args:
        update()
        return 0
    report = None
    if "--report" in args:
        report = args[args.index("--report") + 1]
    problems = check(report)
    if problems:
        print("TUNING REGRESSION:")
        for p in problems:
            print(f"  - {p}")
        print("(intentional change? regenerate with: PYTHONPATH=src "
              "python benchmarks/check_tuning_baseline.py --update)")
        return 1
    print(f"tuning baseline OK ({BASELINE_PATH.name}"
          + (f", report {report} consistent" if report else "") + ")")
    return 0


if __name__ == "__main__":
    sys.exit(main())
