"""Benchmark harness — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run

Output: ``name,us_per_call,derived`` CSV rows.
  table2_3mm          — paper Table 2 (generated 3MM schedule)
  fig4_advancedload   — paper Fig. 4 (upload hoisting)
  fig5_delegatestore  — paper Fig. 5 (download sinking)
  fig6_<problem>      — paper Fig. 6 (Polybench suite speedups)
  train_overlap       — beyond-paper: planner schedule on the train loop
  roofline summary    — see EXPERIMENTS.md §Roofline (from the dry-run)
"""
from __future__ import annotations

def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")

    from benchmarks import table2_3mm
    row = table2_3mm.run(show_source=False)
    extra = ";".join(
        f"{k}={v if not isinstance(v, float) else round(v, 2)}"
        for k, v in row.items() if k != "wall_opt_ms")
    print(f"table2_3mm,{row['wall_opt_ms'] * 1e3:.0f},{extra}")

    from benchmarks import directive_micro
    for bench in (directive_micro.bench_advancedload,
                  directive_micro.bench_delegatestore):
        r = bench()
        extra = ";".join(f"{k}={v if not isinstance(v, float) else round(v, 2)}"
                         for k, v in r.items()
                         if k not in ("name", "t_opt_ms"))
        print(f"{r['name']},{r['t_opt_ms'] * 1e3:.0f},{extra}")

    from benchmarks import polybench_suite
    for r in polybench_suite.run_suite():
        print(f"fig6_{r['problem']},{r['t_omp2hmpp_ms'] * 1e3:.0f},"
              f"speedup_seq={r['speedup_vs_seq']:.2f}x;"
              f"speedup_naive={r['speedup_vs_naive']:.2f}x;"
              f"hand_gap={r['hand_vs_omp2hmpp']:.2f}x;"
              f"transfers={r['transfers_opt']}/{r['transfers_naive']};"
              f"bytes_saved={r['bytes_saved_vs_naive']}")

    from benchmarks import train_overlap
    r = train_overlap.run()
    print(f"{r['name']},"
          f"{r['t_planned_ms'] * 1e3 / train_overlap.STEPS:.0f},"
          f"speedup={r['speedup']:.2f}x;sync_ms={r['t_sync_ms']:.0f};"
          f"planned_ms={r['t_planned_ms']:.0f};"
          f"final_loss={r['final_loss']:.3f}")


if __name__ == "__main__":
    main()
