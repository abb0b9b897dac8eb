"""The decode program's device time in a profiler trace, split by the named
scope of each block kind (``jax.named_scope`` of ``mamba2``, ``moe`` and
``attn`` in ``repro.models.transformer``).

The device trace names each op by its instruction in the compiled program
(``%fusion.12 = ...``) and carries no scope.  The compiled program's text
does: each instruction's ``metadata={op_name="jit(...)/mamba2/..."}``.
``op_scopes`` reads that map from the program's text; ``attribute`` sums,
over the executions of the decode program inside the traced window, the
device time of the top-level ops of each scope.  What no scope claims (ops
outside the blocks, such as the embedding and the head, and gaps between
ops inside an execution) goes to ``other``, so the scopes and ``other``
add up to the decode executions' device time.  Beside that split it gives
each scope's span: the union of the intervals of its ops and of the
asynchronous copies that fetch its operands (weights prefetched while
another block computes), the time over which its work was done.

    python -m bench.scope_trace read <trace dir or .xplane.pb> <hlo.txt[.gz]>
    python -m bench.scope_trace record <dir>

``read`` prints the attribution as one JSON object; ``record`` traces a
small Nemotron-H decode through the serving engine (on the chip) and
writes its trace and its decode program's text under ``<dir>``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys
from pathlib import Path
from typing import Dict, Iterable, Optional

from bench import xplane

SCOPES = ("mamba2", "moe", "attn")
DECODE_MODULE = "jit__decode_impl"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="(?P<path>[^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_ASYNC = re.compile(r"-(start|done)(\.\d+)?$")
_HOPS = 6
ASYNC_LINE = "Async XLA Ops"


def op_scopes(hlo_text: str, scopes: Iterable[str] = SCOPES
              ) -> Dict[str, str]:
    """{instruction name: scope} of every instruction whose op_name path
    has one of ``scopes`` as a component (the innermost, where nested).
    An asynchronous copy or slice (``*-start``, ``*-done``) takes the
    scope of the first instruction that uses what it fetched, found
    through users that have no scope of their own (a bitcast, a concat
    of slices), or else of the weight it fetches (a parameter whose path
    names the scope, as ``params['blocks'][1]['moe']...`` does): a weight
    prefetched while another block runs is that block's work."""
    want = set(scopes)
    scope: Dict[str, str] = {}
    users: Dict[str, list] = {}
    operands: Dict[str, list] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group("name")
        p = _OP_NAME.search(line)
        hit = ([part for part in p.group("path").split("/") if part in want]
               if p else [])
        if hit:
            scope[name] = hit[-1]
        operands[name] = _OPERAND.findall(line.split(" = ", 1)[1])
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)
    for name in [n for n in operands if _ASYNC.search(n) and n not in scope]:
        seen, frontier = {name}, [name]
        for _ in range(_HOPS):
            nxt = [u for n in frontier for u in users.get(n, ())
                   if u not in seen]
            found = next((scope[u] for u in nxt if u in scope), None)
            if found:
                scope[name] = found
                break
            seen.update(nxt)
            frontier = nxt
        else:
            named = [s for o in operands[name] for s in want
                     if f"_{s}_" in o]
            if named:
                scope[name] = named[0]
    return scope


def _op_name(event_name: str) -> str:
    """The instruction name of an op event (``%fusion.12 = ...`` or
    ``fusion.12``)."""
    m = _INSTR.match(event_name)
    return m.group("name") if m else event_name.lstrip("%")


def attribute(path_or_pd, scope_of: Dict[str, str], *,
              module: str = DECODE_MODULE,
              window_span: Optional[str] = None,
              scopes: Iterable[str] = SCOPES) -> Dict[str, object]:
    """Device seconds of ``module``'s executions inside the window, and
    two splits of them, summed over devices:

    - ``seconds``: each op's time to its scope, ``other`` the rest, so
      that they add up to ``device_s``.  Ops nested inside another op
      (the body of a loop) count as part of their parent, and where two
      ops overlap the shared time counts once, for the one that began
      first.
    - ``spans``: per scope, the time in which its ops or its asynchronous
      copies ran (their union), so a weight prefetched while another
      block computes counts for both; these may add up to more than
      ``device_s``, and bound from above the time a scope's work took.
    """
    pd = path_or_pd
    if isinstance(pd, str):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(pd if not os.path.isdir(pd)
                                   else xplane.find_xplane(pd))
    summary = xplane.summarize(pd, window_span=window_span)
    execs = summary.executions_of(module=module)
    seconds = {s: 0.0 for s in scopes}
    spans = {s: 0.0 for s in scopes}
    device_ns = 0.0
    for plane in pd.planes:
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        mine = sorted((e.start_ns, e.end_ns) for e in execs
                      if e.device == plane.name)
        if not mine:
            continue
        lines = {line.name: line for line in plane.lines}
        ops = _events(lines.get(xplane.OPS_LINE), scope_of)
        copies = _events(lines.get(ASYNC_LINE), scope_of)
        i = j = 0
        for lo, hi in mine:
            device_ns += hi - lo
            top_end = lo
            held = {s: [] for s in spans}
            while i < len(ops) and ops[i][0] < lo:
                i += 1
            while i < len(ops) and ops[i][0] < hi:
                a, b, scope = ops[i]
                i += 1
                if scope in held:
                    held[scope].append((a, min(b, hi)))
                if b <= top_end:            # inside an earlier op
                    continue
                # time already covered belongs to the op that began first
                a, top_end = max(a, top_end), b
                if scope in seconds:
                    seconds[scope] += (min(b, hi) - a) * 1e-9
            while j < len(copies) and copies[j][0] < lo:
                j += 1
            while j < len(copies) and copies[j][0] < hi:
                a, b, scope = copies[j]
                j += 1
                if scope in held:
                    held[scope].append((a, min(b, hi)))
            for scope, iv in held.items():
                spans[scope] += sum(b - a for a, b in xplane.union(iv)) * 1e-9
    device_s = device_ns * 1e-9
    seconds["other"] = device_s - sum(seconds.values())
    return {"executions": len(execs), "device_s": device_s,
            "seconds": seconds, "spans": spans}


def _events(line, scope_of):
    """(start_ns, end_ns, scope or None) of a line's events, by start
    (the longer first where two start together)."""
    if line is None:
        return []
    return sorted(((ev.start_ns, ev.start_ns + ev.duration_ns,
                    scope_of.get(_op_name(ev.name)))
                   for ev in line.events), key=lambda o: (o[0], -o[1]))


def _read_text(path: str) -> str:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return f.read()


def record(out_dir: str) -> None:
    """Trace a small Nemotron-H (every block kind, bf16) serving a few
    requests through ``Engine``, with the decode program's text beside
    the trace."""
    import dataclasses

    import jax
    import numpy as np

    src = str(Path(__file__).resolve().parents[1] / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.configs import get_config, reduced
    from repro.serve import Engine, Request, ServeRuntime

    cfg = dataclasses.replace(
        reduced(get_config("nemotron3-nano-30b-a3b")), d_model=256,
        mamba_heads=8, mamba_head_dim=32, ssm_state=64, vocab=1024,
        dtype="bfloat16")
    rt = ServeRuntime(cfg, max_seq=64)
    eng = Engine(rt, capacity=4)
    rng = np.random.default_rng(0)

    def batch(start):
        return [Request(rid=start + i, max_new_tokens=6,
                        prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32))
                for i in range(4)]

    eng.run(batch(0), respect_arrivals=False)             # compiles
    os.makedirs(out_dir, exist_ok=True)
    with jax.profiler.trace(os.path.join(out_dir, "trace")):
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.run(batch(4), respect_arrivals=False)
    C = eng.capacity
    row = jax.ShapeDtypeStruct((C,), np.int32)
    hlo = rt._decode.lower(rt.params, eng.pool.cache, row, row,
                           jax.ShapeDtypeStruct((C, 6), np.int32),
                           row).compile().as_text()
    with gzip.open(os.path.join(out_dir, "decode.hlo.txt.gz"), "wt") as f:
        f.write(hlo)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    r = sub.add_parser("read")
    r.add_argument("trace")
    r.add_argument("hlo")
    r.add_argument("--window-span", default="bench.window")
    rec = sub.add_parser("record")
    rec.add_argument("dir")
    args = ap.parse_args(argv)
    if args.cmd == "record":
        record(args.dir)
        return 0
    print(json.dumps(attribute(args.trace, op_scopes(_read_text(args.hlo)),
                               window_span=args.window_span)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
