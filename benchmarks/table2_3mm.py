"""Table 2 analogue: the generated 'source' for 3MM + its schedule stats.

Prints the HMPP-style emission (group/mapbyname/advancedload/async
callsites/noupdate/synchronize/delegatedstore/release — the same directive
structure as the paper's generated listing) and the measured transfer
schedule vs the naive policy.
"""
from __future__ import annotations

from repro.core import emit, execute, naive_plan, plan, transfer_summary
from repro.launch.compile_cache import use_compile_cache
from repro.polybench import build


def run(n: int = 512, show_source: bool = True):
    p, _ = build("3mm", n=n)
    opt = plan(p)
    if show_source:
        print(emit(opt))
        print()
    execute(opt)                    # warm the jit caches
    execute(naive_plan(p))
    _, s_opt = execute(opt)
    _, s_nv = execute(naive_plan(p))
    summary = transfer_summary(opt)
    row = {
        "loads_opt": s_opt.h2d_transfers, "loads_naive": s_nv.h2d_transfers,
        "stores_opt": s_opt.d2h_transfers,
        "stores_naive": s_nv.d2h_transfers,
        "noupdate_args": summary["noupdate_args"],
        "bytes_opt": s_opt.h2d_bytes + s_opt.d2h_bytes,
        "bytes_naive": s_nv.h2d_bytes + s_nv.d2h_bytes,
        "wall_opt_ms": s_opt.wall_time * 1e3,
        "wall_naive_ms": s_nv.wall_time * 1e3,
    }
    return row


def main():
    use_compile_cache()
    row = run(show_source=True)
    extra = ";".join(f"{k}={v if not isinstance(v, float) else round(v,2)}"
                     for k, v in row.items() if k != "wall_opt_ms")
    print(f"table2_3mm,{row['wall_opt_ms'] * 1e3:.0f},{extra}")
    return row


if __name__ == "__main__":
    main()
