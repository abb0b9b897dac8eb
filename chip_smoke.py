#!/usr/bin/env python3
"""Bring-up proof on a TPU: drive the planner and serving paths once, at
real sizes, through the entry points a user calls, and check the results.

    python chip_smoke.py               # one chip: kernels, planner, serve
    python chip_smoke.py --chips 4     # four chips: the mesh phase alone

Phases (one JSON line per result, each with sizes, the error against its
reference, compile seconds and run seconds):

* ``kernels``: flash_attention at qwen2.5-14b's layout, wkv6 at
  rwkv6-3b's, rglru_scan at recurrentgemma-2b's width and rmsnorm at
  d = 5120, compiled natively and compared with ``kernels/ref.py``.
* ``planner``: Polybench 3mm and gemm at n = 2048 and jacobi2d at n = 2800
  (square approximations of the EXTRALARGE datasets), each under the
  optimized and the naive plan, executed compiled on the ``jax`` and the
  ``pinned`` backends against the host oracle; 3mm tuned on the chip
  (``policy="auto"``); the kernel-tagged attention step program at
  qwen2.5-14b's layout.
* ``serve``: rwkv6-3b at its published widths in bf16 through
  ``ServeRuntime`` + ``Engine``, with one request's prefill logits compared
  against an fp32 reference of the same weights run on the host CPU (the
  bounds are at ``SERVE_DRIFT_RATIO`` below).
* ``mesh`` (``--chips 4`` only): 3mm tuned over the replicate / fsdp / tp
  placements of the ``mesh`` backend, beside the single-chip ``jax`` plan.

The script exits non-zero when no TPU is found or any phase fails.  Only
when every phase passed is its last line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# bf16 keeps 8 significant bits (3.9e-3 relative per rounding), and
# rwkv6-3b keeps its activations in bf16 between ops.  With random weights
# every layer moves the residual stream by about its own size, so the
# roundings add up layer after layer: on the CPU the bf16 prefill logits
# of rwkv6-3b's widths drift from fp32 by 1.2% (max|chip - ref| / max|ref|)
# after 1 layer, 2.4% after 2, 5.4% after 4, 15% after 8 and 25% after 12.
# So the full 32-layer stack is held to the drift of the same bf16 model
# run on the host CPU: its L2 error against fp32 may be at most
# SERVE_DRIFT_RATIO times the host's (and never needs to beat
# SERVE_LOGIT_REL_BOUND).  The first REF_PREFIX_LAYERS layers of the same
# resident weights, where rounding is still small, are held to
# SERVE_LOGIT_REL_BOUND outright.
REF_PREFIX_LAYERS = 2
SERVE_LOGIT_REL_BOUND = 0.05
SERVE_DRIFT_RATIO = 1.5


def _emit(rec: dict) -> None:
    print(json.dumps(rec, default=float), flush=True)


class CompileClock:
    """Seconds JAX spent compiling (or reading a compiled program back
    from the persistent cache), and the cache hits, while it is open."""

    _EVENT = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == self._EVENT:
            self.seconds += duration

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


def _err(out, ref) -> dict:
    import numpy as np
    a = np.asarray(out, np.float64)
    b = np.asarray(ref, np.float64)
    diff = np.abs(a - b)
    return {"max_abs_err": float(diff.max()),
            "max_rel_err": float(diff.max() / max(np.abs(b).max(), 1e-30)),
            "l2_rel_err": float(np.linalg.norm(diff)
                                / max(np.linalg.norm(b), 1e-30))}


def _close(out, ref, *, rtol: float, atol: float) -> dict:
    """``max|out - ref| <= atol + rtol * max|ref|``: the tolerance scales
    with the output's range, not with each element, because a chunked
    kernel's rounding follows the largest values it carries (wkv6's
    state), not the element being compared."""
    import numpy as np
    rec = _err(out, ref)
    bound = atol + rtol * float(np.abs(np.asarray(ref, np.float64)).max())
    rec.update(rtol=rtol, atol=atol,
               ok=bool(np.isfinite(np.asarray(out, np.float64)).all()
                       and rec["max_abs_err"] <= bound))
    return rec


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def phase_kernels(*, flash=(1, 2048, 8, 5, 128), wkv=(1, 2048, 40, 64),
                  rglru=(1, 2048, 2560), rmsnorm=(2048, 5120),
                  interpret: bool = False, seed: int = 0) -> list:
    """Each kernel of ``kernels/ops.py`` against ``kernels/ref.py``.

    ``flash`` is (B, S, K, G, D) with S = T; ``wkv`` is (B, T, H, hs);
    ``rglru`` is (B, T, D); ``rmsnorm`` is (rows, D).  The kernels' fp32
    dots may run as bf16 passes on the MXU, which the flash and wkv6
    tolerances allow for; the references run at full fp32 precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16

    def arr(shape, dtype, lo=None, hi=None):
        x = (rng.uniform(lo, hi, shape) if lo is not None
             else rng.standard_normal(shape))
        return jnp.asarray(x.astype(np.float32), dtype)

    B, S, K, G, D = flash
    H, hs = wkv[2:]
    cases = [
        ("flash_attention", dict(q=flash, kv=(B, S, K, D), dtype="bf16"),
         lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                             interpret=interpret),
         lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True),
         (arr(flash, bf16), arr((B, S, K, D), bf16),
          arr((B, S, K, D), bf16)), dict(rtol=2e-2, atol=2e-2)),
        ("wkv6", dict(rkvw=wkv, dtype="bf16 r,k,v,u; f32 w"),
         lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u, interpret=interpret),
         _wkv6_ref,
         (arr(wkv, bf16), arr(wkv, bf16), arr(wkv, bf16),
          arr(wkv, jnp.float32, 0.3, 0.99), arr((H, hs), bf16)),
         dict(rtol=1e-2, atol=1e-2)),
        ("rglru_scan", dict(ab=rglru, dtype="f32"),
         lambda a, b: ops.rglru_scan(a, b, interpret=interpret),
         ref.rglru_scan_ref,
         (arr(rglru, jnp.float32, 0.4, 0.999), arr(rglru, jnp.float32)),
         dict(rtol=1e-3, atol=1e-3)),
        ("rmsnorm", dict(x=rmsnorm, dtype="bf16"),
         lambda x, w: ops.rmsnorm(x, w, interpret=interpret),
         ref.rmsnorm_ref,
         (arr(rmsnorm, bf16), arr(rmsnorm[-1:], bf16)),
         dict(rtol=2e-2, atol=2e-2)),
    ]
    out = []
    for name, sizes, fn, ref_fn, args, tol in cases:
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref_fn)(*args)
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        checks = [_close(g, w, **tol) for g, w in zip(got, want)]
        rec = {"phase": "kernels", "kernel": name, "sizes": sizes,
               "interpret": interpret,
               "max_abs_err": max(c["max_abs_err"] for c in checks),
               "max_rel_err": max(c["max_rel_err"] for c in checks),
               **tol, "compile_s": compile_s, "run_s": run_s,
               "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
               "ok": all(c["ok"] for c in checks)}
        if not interpret:
            rec["ok"] = rec["ok"] and rec["tpu_custom_call"]
        out.append(rec)
    return out


def _wkv6_ref(r, k, v, w, u):
    """The sequential reference at the ops layout (B, T, H, hs)."""
    import jax.numpy as jnp

    from repro.kernels import ref
    B, T, H, hs = r.shape

    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, T, hs)

    uu = jnp.broadcast_to(u[None], (B, H, hs)).reshape(B * H, hs)
    o, s = ref.wkv6_ref(fold(r), fold(k), fold(v), fold(w), uu)
    return (o.reshape(B, H, T, hs).transpose(0, 2, 1, 3),
            s.reshape(B, H, hs, hs))


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def _transfers(stats) -> dict:
    return {k: stats.as_dict()[k] for k in
            ("h2d_transfers", "h2d_bytes", "d2h_transfers", "d2h_bytes",
             "kernel_calls", "fused_launches")}


def _timed_execute(pl, **kw):
    """Execute ``pl`` twice: the first call's compile seconds, and the
    second (steady) call's outputs and stats."""
    from repro.core import execute
    with CompileClock() as clock:
        execute(pl, **kw)
    return clock.seconds, execute(pl, **kw)


def phase_planner(*, n_mm: int = 2048, n_jacobi: int = 2800,
                  attn: dict = None, interpret: bool = False,
                  rtol: float = 1e-3) -> list:
    """Planned programs on the ``jax`` and ``pinned`` backends against
    the host oracle, the tuned 3mm, and the attention step program.
    fp32 programs run their matmuls at full fp32 precision so that they
    compare with numpy's."""
    import jax
    import numpy as np

    from repro.core import (naive_plan, plan, run_host_oracle,
                            transfer_summary, winner_exec_kwargs)
    from repro.optim.offload import host_memory_kind
    from repro.polybench import build

    if attn is None:
        attn = dict(seq=2048, kv_heads=8, group=5, head_dim=128)
    kind = host_memory_kind()
    out = [{"phase": "planner", "check": "host_memory_kind", "kind": kind,
            "ok": kind == "pinned_host"}]
    sizes = {"3mm": n_mm, "gemm": n_mm, "jacobi2d": n_jacobi}
    note = ("square approximations of Polybench/C 4.2 EXTRALARGE: 3mm "
            "1600..2400, gemm 2000..2600, jacobi2d N=2800 (20 sweeps of "
            "its 1000)")

    with jax.default_matmul_precision("highest"):
        for name, n in sizes.items():
            p, _ = build(name, n=n)
            oracle = np.asarray(run_host_oracle(p)["out"], np.float64)
            for policy, pl in (("optimized", plan(p)),
                               ("naive", naive_plan(p))):
                for backend in ("jax", "pinned"):
                    compile_s, (res, st) = _timed_execute(
                        pl, mode="compiled", backend=backend)
                    err = _err(res["out"], oracle)["max_rel_err"]
                    out.append({
                        "phase": "planner", "program": name, "n": n,
                        "sizes_note": note, "policy": policy,
                        "backend": backend,
                        "plan": transfer_summary(pl),
                        "transfers": _transfers(st), "max_rel_err": err,
                        "rtol": rtol, "compile_s": compile_s,
                        "run_s": st.wall_time, "ok": err <= rtol})

        # tuned on the device: refresh forces fresh measurements
        p, _ = build("3mm", n=n_mm)
        t0 = time.perf_counter()
        tuned = plan(p, policy="auto", backend="jax", refresh=True)
        tune_s = time.perf_counter() - t0
        compile_s, (res, st) = _timed_execute(
            tuned, **winner_exec_kwargs(tuned, "jax"))
        err = _err(res["out"], run_host_oracle(p)["out"])["max_rel_err"]
        tuning = tuned.meta["tuning"]
        n_measured = tuned.meta["tuning_cache"]["measurements"]
        chosen = next(c for c in tuning["candidates"]
                      if c["label"] == tuning["chosen"])
        out.append({"phase": "planner", "program": "3mm", "n": n_mm,
                    "policy": "auto", "backend": "jax",
                    "chosen": tuning["chosen"],
                    "n_candidates": len(tuning["candidates"]),
                    "n_measured": n_measured, "tune_s": tune_s,
                    "winner_measured_s": chosen["measured_s"],
                    "transfers": _transfers(st), "max_rel_err": err,
                    "rtol": rtol, "compile_s": compile_s,
                    "run_s": st.wall_time,
                    "ok": err <= rtol and n_measured > 0})

    # the kernel-tagged attention step at its own precision (bf16 in)
    out.append(_attention_step(attn, interpret))
    return out


def _attention_step(attn: dict, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import plan
    from repro.kernels import ref
    from repro.optim.offload import attention_step_program

    p = attention_step_program(dtype=jnp.bfloat16, interpret=interpret,
                               **attn)
    pl = plan(p)
    compile_s, (res, st) = _timed_execute(pl, mode="compiled", backend="jax")
    q, k, v = (jnp.asarray(p.inputs[n]) for n in "qkv")
    with jax.default_matmul_precision("highest"):
        o = ref.flash_attention_ref(q, k, v, causal=True)
        want = float(jnp.square(o.astype(jnp.float32)).sum()) * 1.001
    got = float(np.asarray(res["final_loss"]).reshape(-1)[0])
    err = abs(got - want) / abs(want)
    return {"phase": "planner", "program": "attention_step",
            "sizes": {"q": list(p.inputs["q"].shape), "dtype": "bf16"},
            "kernel_variants": pl.meta.get("kernel_variants"),
            "transfers": _transfers(st), "max_rel_err": err, "rtol": 1e-2,
            "compile_s": compile_s, "run_s": st.wall_time,
            "ok": err <= 1e-2}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def phase_serve(cfg=None, *, n_requests: int = 8, capacity: int = 4,
                max_seq: int = 512, seed: int = 0) -> list:
    """``cfg`` (rwkv6-3b at its published widths when None) served through
    ``ServeRuntime`` + ``Engine`` on a seeded trace; one request's bf16
    prefill logits against an fp32 reference of the same weights on the
    host CPU (an fp32 copy does not fit beside the bf16 weights on a
    16 GB chip)."""
    import jax

    from repro.configs import get_config
    from repro.serve import Engine, RequestState, ServeRuntime, make_trace

    if cfg is None:
        cfg = get_config("rwkv6-3b")
    out = []
    t0 = time.perf_counter()
    rt = ServeRuntime(cfg, max_seq=max_seq, seed=seed)
    jax.block_until_ready(rt.params)
    init_s = time.perf_counter() - t0
    eng = Engine(rt, capacity=capacity)
    reqs = make_trace(cfg, n_requests=n_requests, rate_rps=50.0,
                      seed=seed, max_seq=max_seq)
    with CompileClock() as clock:
        rep = eng.run(reqs)
    eng.pool.assert_no_leaks()
    done = {r.rid: r for r in eng.completed}
    finished = all(r.rid in done and done[r.rid].state is
                   RequestState.FINISHED and
                   len(done[r.rid].tokens) == r.max_new_tokens
                   for r in reqs)
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    out.append({"phase": "serve", "arch": cfg.name,
                "sizes": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                          "d_ff": cfg.d_ff, "vocab": cfg.vocab,
                          "dtype": cfg.dtype,
                          "weights_bytes": rt.weights_bytes},
                "n_requests": n_requests, "capacity": capacity,
                "max_seq": max_seq,
                "prompt_lens": [r.prompt_len for r in reqs],
                "gen_lens": [r.max_new_tokens for r in reqs],
                "init_s": init_s, "compile_s": clock.seconds,
                "run_s": rep["wall_s"] - clock.seconds,
                "wall_s": rep["wall_s"], "gen_tokens": rep["gen_tokens"],
                "peak_device_bytes": mem.get("peak_bytes_in_use"),
                "pool": rep["pool"], "ok": finished})

    # request 0's prefill logits, chip bf16 against host fp32 on the same
    # weights: the full stack within SERVE_DRIFT_RATIO of the host bf16
    # model's own drift, its first REF_PREFIX_LAYERS layers within
    # SERVE_LOGIT_REL_BOUND
    host = jax.tree.unflatten(
        jax.tree.structure(rt.params),
        [rt.residency.fetch(f"w{i:04d}")
         for i in range(len(jax.tree.leaves(rt.params)))])
    for depth in sorted({cfg.n_layers, min(REF_PREFIX_LAYERS,
                                           cfg.n_layers)}, reverse=True):
        rec = _logits_vs_fp32(cfg, rt.params, host, reqs[0], depth, max_seq)
        if depth <= REF_PREFIX_LAYERS:
            on, bound = "max_rel_err", SERVE_LOGIT_REL_BOUND
        else:
            on, bound = "l2_rel_err", max(
                SERVE_DRIFT_RATIO * rec["host_bf16"]["l2_rel_err"],
                SERVE_LOGIT_REL_BOUND)
        rec.update(bound=bound, bound_on=on,
                   ok=rec["finite"] and rec[on] <= bound)
        out.append(rec)
    return out


def _logits_vs_fp32(cfg, params, host, req, depth: int, max_seq: int):
    """Prefill logits of the first ``depth`` layers of ``params`` (bf16,
    on the default device) against the same weights upcast to fp32 on the
    host CPU at full matmul precision; ``host_bf16`` is the same bf16
    model's own error against that reference on the host CPU."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import Transformer

    def prefix(tree):
        if depth == cfg.n_layers:
            return tree
        return dict(tree, layers=jax.tree.map(lambda t: t[:depth],
                                              tree["layers"]))

    model = Transformer(dataclasses.replace(cfg, n_layers=depth))

    def fwd(p, b, lp):
        return model.prefill(p, b, max_seq=max_seq, last_pos=lp)[0]

    tokens = np.asarray(req.prompt, np.int32)[None]
    last = np.asarray([req.prompt_len - 1], np.int32)
    t0 = time.perf_counter()
    got = np.asarray(jax.jit(fwd)(prefix(params),
                                  {"tokens": jnp.asarray(tokens)},
                                  jnp.asarray(last)), np.float64)
    chip_s = time.perf_counter() - t0
    cpu = jax.devices("cpu")[0]

    def on_cpu(dtype):
        # one weight copy on the host at a time (rwkv6-3b: 12.3 GB in fp32)
        p = jax.tree.map(lambda h: jax.device_put(np.asarray(h, dtype), cpu),
                         prefix(host))
        return np.asarray(jax.jit(fwd)(p, {"tokens": jax.device_put(tokens,
                                                                    cpu)},
                                       jax.device_put(last, cpu)), np.float64)

    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        want = on_cpu(np.float32)
    ref_s = time.perf_counter() - t0
    host_bf16 = on_cpu(None)
    return {"phase": "serve", "check": "prefill_logits_vs_fp32_cpu",
            "arch": cfg.name, "layers": depth, "prompt_len": req.prompt_len,
            **_err(got, want), "finite": bool(np.isfinite(got).all()),
            "argmax_agree": bool(got.argmax() == want.argmax()),
            "host_bf16": _err(host_bf16, want),
            "chip_s": chip_s, "reference_s": ref_s}


# ---------------------------------------------------------------------------
# mesh (four chips)
# ---------------------------------------------------------------------------

def phase_mesh(*, n: int = 2048, rtol: float = 1e-3, reps: int = 3) -> list:
    """3mm tuned over the mesh backend's replicate / fsdp / tp placements
    (the placement axis alone), verified, executed against the host
    oracle and timed beside the single-chip ``jax`` plan."""
    import jax
    import numpy as np

    from repro.core import (execute, plan, run_host_oracle, verify_plan,
                            winner_exec_kwargs)
    from repro.core.backend import get_backend
    from repro.distributed.mesh_backend import placement_specs
    from repro.polybench import build

    n_dev = len(jax.devices())
    with jax.default_matmul_precision("highest"):
        be = get_backend("mesh")
        p, _ = build("3mm", n=n)
        oracle = np.asarray(run_host_oracle(p)["out"], np.float64)
        t0 = time.perf_counter()
        tuned = plan(p, policy="auto", backend=be, refresh=True,
                     policies=("optimized",), streams=(2,), fuse=(True,),
                     donate=(True,))
        tune_s = time.perf_counter() - t0
        tuning = tuned.meta["tuning"]
        chosen = next(c for c in tuning["candidates"]
                      if c["label"] == tuning["chosen"])
        by_placement = {c["config"]["mesh_placement"]: c["measured_s"]
                        for c in tuning["candidates"]
                        if c.get("measured_s") is not None}
        verified = verify_plan(tuned).ok
        compile_s, (res, st) = _timed_execute(
            tuned, **winner_exec_kwargs(tuned, be))
        err = _err(res["out"], oracle)["max_rel_err"]

        # every sharded placement's variables, placed by the backend's
        # own upload: each must span all devices without replicating
        spans = {}
        for pol in ("fsdp", "tp"):
            specs, _ = placement_specs(p.inputs, be.mesh, pol)
            twin = be.with_placement(specs)
            for var, spec in sorted(specs.items()):
                if any(e is not None for e in spec):
                    arr = twin.upload(p.inputs[var], name=var)
                    spans[f"{pol}:{var}"] = {
                        "spec": list(spec),
                        "devices": len(arr.sharding.device_set),
                        "replicated": arr.sharding.is_fully_replicated}
                    twin.free(arr)

        single = plan(p)
        times = []
        for _ in range(reps + 1):
            _, s1 = execute(single, mode="compiled", backend="jax")
            times.append(s1.wall_time)
    single_s = min(times[1:])
    spans_ok = bool(spans) and all(
        s["devices"] == n_dev and (n_dev == 1 or not s["replicated"])
        for s in spans.values())
    return [{"phase": "mesh", "program": "3mm", "n": n,
             "mesh": list(be.mesh_desc[0]), "axes": list(be.mesh_desc[1]),
             "placement": (tuned.meta.get("mesh") or {}).get(
                 "placement", "replicate"),
             "chosen": tuning["chosen"],
             "measured_s_by_placement": by_placement,
             "winner_measured_s": chosen["measured_s"],
             "single_chip_jax_s": single_s,
             "predicted_coll_bytes": chosen.get("coll_bytes"),
             "tune_s": tune_s, "verified": verified,
             "sharded_vars": spans, "max_rel_err": err, "rtol": rtol,
             "compile_s": compile_s, "run_s": st.wall_time,
             "ok": verified and err <= rtol and spans_ok}]


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def _run_phase(name: str, fn) -> bool:
    t0 = time.perf_counter()
    try:
        with CompileClock() as clock:
            recs = fn()
    except Exception:
        traceback.print_exc()
        _emit({"phase": name, "ok": False, "error": "exception",
               "wall_s": time.perf_counter() - t0})
        return False
    for rec in recs:
        _emit(rec)
    ok = all(r["ok"] for r in recs)
    _emit({"phase": name, "summary": True, "ok": ok,
           "compile_s": clock.seconds, "cache_hits": clock.cache_hits,
           "wall_s": time.perf_counter() - t0})
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the mesh phase alone over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    # the fp32 serving reference runs on the host CPU of this process
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    # the tuner's and the serve buckets' cache stays inside the checkout
    os.environ.setdefault("REPRO_TUNE_CACHE", str(ROOT / ".tunecache"))
    # before the first compile
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    _emit({"phase": "start", "platform": dev.platform,
           "kind": dev.device_kind, "count": len(devices),
           "compile_cache": cache_dir, "jax": jax.__version__})

    if args.chips == 4:
        phases = [("mesh", phase_mesh)]
    else:
        phases = [("kernels", lambda: phase_kernels(seed=args.seed)),
                  ("planner", phase_planner),
                  ("serve", lambda: phase_serve(seed=args.seed))]
    t0 = time.perf_counter()
    with CompileClock() as clock:
        results = [_run_phase(name, fn) for name, fn in phases]
    _emit({"phase": "total", "compile_s": clock.seconds,
           "cache_hits": clock.cache_hits,
           "wall_s": time.perf_counter() - t0})
    if not all(results):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
